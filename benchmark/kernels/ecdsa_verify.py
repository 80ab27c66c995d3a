"""ECDSA-P256 verification, one dispatch of ``lanes`` signatures.

Textbook (FIPS 186, double-and-add with Shamir's trick): w = s^-1 mod n
(Fermat), u1 = e*w and u2 = r*w (2 multiplications), R = u1*G + u2*Q over
256 bits (256 doublings, an addition for three bit pairs in four), R to
affine (one inversion, 2 multiplications), compare with r.
"""

import contextlib
import hashlib

from benchmark.kernels import p256_textbook as tb

# How the program's jit names the kernel in a profiler trace (XLA Modules).
TRACE_NAME = "jit__verify_one_packed"
# The engine queue whose batches are its dispatches: the key of the engine's
# public ``stats`` (``sign_stats`` for a sign kernel), and which side of it.
QUEUE = "ecdsa_p256"
KIND = "verify"
# Dispatches of it in one profiler session: one fills the device's trace buffer.
CALIBRATION_RUNS = 1

FIELD_MULS = (
    tb.FERMAT_INVERSE + 2
    + tb.BITS * tb.DOUBLE + (3 * tb.BITS // 4) * tb.MIXED_ADD
    + tb.FERMAT_INVERSE + 2
)


def work(lanes: int) -> dict:
    """-> int8 operations and bytes in and out of one dispatch.  In: the
    public key (64), the digest (32), r and s (64); out: one verdict."""
    return {
        "ops": lanes * FIELD_MULS * tb.OPS_PER_FIELD_MUL,
        "peak": "int8_ops_per_s",
        "bytes": lanes * (64 + 32 + 64 + 1),
    }


async def dispatch_once(engine, salt: bytes) -> None:
    """One dispatch through ``engine``'s verify queue: a fresh valid item
    (no memo hit), which the engine pads to its bucket."""
    from minbft_tpu.utils import hostcrypto

    d, q = hostcrypto.keygen()
    digest = hashlib.sha256(salt).digest()
    if not await engine.verify_ecdsa_p256(q, digest, hostcrypto.ecdsa_sign(d, digest)):
        raise RuntimeError("calibration: the device rejected a valid signature")


@contextlib.contextmanager
def skip():
    """The control ``verify_skipped``: while entered, this kernel answers
    "valid" in every lane (patched at the module-level entry the engine's
    dispatcher looks up on every call)."""
    import numpy as np

    from minbft_tpu.ops import p256

    kernel = p256.ecdsa_verify_kernel_packed
    p256.ecdsa_verify_kernel_packed = lambda packed: np.ones(packed.shape[0], bool)
    try:
        yield
    finally:
        p256.ecdsa_verify_kernel_packed = kernel
