"""The device part of ECDSA-P256 signing, one dispatch of ``lanes`` nonces:
the fixed-base scalar multiplication k*G (the host derives k and finishes
s = k^-1 (e + r d), and the engine counts that under host prep).

Textbook (double-and-add): 256 doublings and an addition for every second
bit.  The result leaves the device projective (x, z).
"""

import hashlib

from benchmark.kernels import p256_textbook as tb

TRACE_NAME = "jit__kg_comb_widen"
QUEUE = "ecdsa_p256"  # the key of the engine's ``sign_stats``
KIND = "sign"
CALIBRATION_RUNS = 2

FIELD_MULS = tb.BITS * tb.DOUBLE + (tb.BITS // 2) * tb.MIXED_ADD


def work(lanes: int) -> dict:
    """In: the nonce (32); out: x and z (64)."""
    return {
        "ops": lanes * FIELD_MULS * tb.OPS_PER_FIELD_MUL,
        "peak": "int8_ops_per_s",
        "bytes": lanes * (32 + 64),
    }


async def dispatch_once(engine, salt: bytes) -> None:
    """One dispatch through ``engine``'s sign queue."""
    from minbft_tpu.utils import hostcrypto

    d, _q = hostcrypto.keygen()
    await engine.sign_ecdsa_p256(d, hashlib.sha256(salt).digest())
