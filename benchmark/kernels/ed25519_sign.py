"""The device part of Ed25519 signing, one dispatch of ``lanes`` nonces:
the fixed-base scalar multiplication r*B (the host derives r and k by
SHA-512, compresses R and finishes s = r + k*a, and the engine counts that
under host prep).

Textbook (double-and-add): 253 doublings and an addition for every second
bit.  The result leaves the device in extended coordinates (X, Y, Z).
"""

import hashlib

from benchmark.kernels import ed25519_textbook as tb
from benchmark.kernels.p256_textbook import OPS_PER_FIELD_MUL

from minbft_tpu.ops import ed25519

# "jit__rb_comb_widen" since PR 29 ("jit_widen" before): from the program,
# as ed25519_verify.py takes its own.
TRACE_NAME = "jit_" + ed25519.rb_comb_kernel().__name__
QUEUE = "ed25519"  # the key of the engine's ``sign_stats``
KIND = "sign"
CALIBRATION_RUNS = 2

FIELD_MULS = tb.SCALAR_BITS * tb.DOUBLE + (tb.SCALAR_BITS // 2) * tb.ADD


def work(lanes: int) -> dict:
    """In: the nonce (32); out: X, Y and Z (96)."""
    return {
        "ops": lanes * FIELD_MULS * OPS_PER_FIELD_MUL,
        "peak": "int8_ops_per_s",
        "bytes": lanes * (32 + 96),
    }


async def dispatch_once(engine, salt: bytes) -> None:
    """One dispatch through ``engine``'s sign queue."""
    from minbft_tpu.utils import hostcrypto

    seed, _pub = hostcrypto.ed25519_keygen()
    await engine.sign_ed25519(seed, hashlib.sha256(salt).digest())
