"""Ed25519 verification, the device's part, one dispatch of ``lanes``
signatures (RFC 8032 5.1.7; k = SHA-512(R || A || M) mod L and the cached
decompression of A are the host's, and the engine counts them under host
prep).

Textbook: [S]B + [k](-A) by Shamir's trick over 253 bits (253 doublings,
an addition for three bit pairs in four), the result to affine (one Fermat
inversion, 2 multiplications), compare with R.  Not the program's 256
doublings and 256 unconditional complete additions: a kernel that does
more than the textbook reads a lower share.
"""

import contextlib
import hashlib

from benchmark.kernels import ed25519_textbook as tb
from benchmark.kernels.p256_textbook import OPS_PER_FIELD_MUL

from minbft_tpu.ops import ed25519

# How the program's jit names the kernel in a profiler trace (XLA Modules):
# "jit__ed25519_verify_one_packed" since PR 29.  Taken from the program, so
# that these files laid over the commit before it, whose jit had ECDSA's
# name, find that commit's events too; tests/benchmark pins the name.
TRACE_NAME = "jit_" + ed25519.ed25519_verify_kernel_packed.__name__
QUEUE = "ed25519"  # the key of the engine's ``stats``
KIND = "verify"
# Dispatches of it in one profiler session: one fills the device's trace buffer.
CALIBRATION_RUNS = 1

FIELD_MULS = (
    tb.SCALAR_BITS * tb.DOUBLE + (3 * tb.SCALAR_BITS // 4) * tb.ADD
    + tb.FERMAT_INVERSE + 2
)


def work(lanes: int) -> dict:
    """-> int8 operations and bytes in and out of one dispatch.  In: A's
    x and y (64), S and k (64), R as signed (32); out: one verdict."""
    return {
        "ops": lanes * FIELD_MULS * OPS_PER_FIELD_MUL,
        "peak": "int8_ops_per_s",
        "bytes": lanes * (64 + 64 + 32 + 1),
    }


async def dispatch_once(engine, salt: bytes) -> None:
    """One dispatch through ``engine``'s verify queue: a fresh valid item
    (no memo hit), which the engine pads to its bucket."""
    from minbft_tpu.utils import hostcrypto

    seed, pub = hostcrypto.ed25519_keygen()
    digest = hashlib.sha256(salt).digest()
    if not await engine.verify_ed25519(pub, digest, hostcrypto.ed25519_sign(seed, digest)):
        raise RuntimeError("calibration: the device rejected a valid signature")


@contextlib.contextmanager
def skip():
    """The control ``verify_skipped``: while entered, this kernel answers
    "valid" in every lane (patched at the module-level entry the engine's
    dispatcher looks up on every call)."""
    import numpy as np

    kernel = ed25519.ed25519_verify_kernel_packed
    ed25519.ed25519_verify_kernel_packed = lambda packed: np.ones(packed.shape[0], bool)
    try:
        yield
    finally:
        ed25519.ed25519_verify_kernel_packed = kernel
