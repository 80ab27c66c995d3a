"""HMAC-SHA256 verification of a 32-byte message under a 32-byte key (a
USIG certificate check), one dispatch of ``lanes`` certificates.

Textbook (RFC 2104 over FIPS 180-4): the key XOR ipad and XOR opad (8 key
words each; the rest of either block is constant), four SHA-256
compressions (two for the inner hash of 96 bytes, two for the outer), and
the comparison of 8 words.  Counted in 32-bit word operations, four 8-bit
operations each against the chip's int8 peak.  A bitwise kernel runs on the
vector unit and cannot reach the matrix unit's peak, so this share is a
floor, as ECDSA's 0.275 % is; by this count the dispatch's bytes, not its
operations, are the larger bound.
"""

import contextlib
import hashlib
import hmac

from benchmark.kernels import sha256_textbook as tb

TRACE_NAME = "jit_hmac_verify_kernel_packed"
QUEUE = "hmac_sha256"  # the key of the engine's ``stats``; certificates are made on the host
KIND = "verify"
# Two small dispatches a session: a lone event of a kernel this short would
# fall under tracing.FLOOR of its dispatch's host time.
CALIBRATION_RUNS = 2

WORD_OPS = 2 * 8 + 4 * tb.COMPRESSION + 8


def work(lanes: int) -> dict:
    """In: the key (32), the message (32), the certificate (32); out: one verdict."""
    return {
        "ops": lanes * WORD_OPS * tb.INT8_OPS_PER_WORD_OP,
        "peak": "int8_ops_per_s",
        "bytes": lanes * (32 + 32 + 32 + 1),
    }


async def dispatch_once(engine, salt: bytes) -> None:
    """One dispatch through ``engine``'s HMAC verify queue: a fresh valid item."""
    key = hashlib.sha256(salt).digest()
    msg = hashlib.sha256(key).digest()
    if not await engine.verify_hmac_sha256(key, msg, hmac.new(key, msg, hashlib.sha256).digest()):
        raise RuntimeError("calibration: the device rejected a valid certificate")


@contextlib.contextmanager
def skip():
    """The control ``verify_skipped``: while entered, this kernel answers
    "valid" in every lane (the engine's dispatcher imports the entry from
    its module on every call)."""
    import numpy as np

    from minbft_tpu.ops import hmac_sha256

    kernel = hmac_sha256.hmac_verify_kernel_packed
    hmac_sha256.hmac_verify_kernel_packed = lambda packed: np.ones(packed.shape[0], bool)
    try:
        yield
    finally:
        hmac_sha256.hmac_verify_kernel_packed = kernel
