"""Multi-chip sharding tests on the 8-virtual-device CPU mesh.

Covers the path the driver's ``dryrun_multichip`` exercises (the batch axis
sharded over a 1-D ``jax.sharding.Mesh``) so sharding regressions are caught
in CI, not only by the driver.  The reference scales by adding gRPC-connected
replicas (reference sample/conn/grpc/); here the data-parallel scale axis is
a sharding annotation over the verification batch (SURVEY.md §2.8).
"""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minbft_tpu.ops import lowering, p256
from minbft_tpu.ops.hmac_sha256 import hmac_sign_kernel
from minbft_tpu.parallel import mesh as mesh_mod
from minbft_tpu.utils import hostcrypto as hc


@pytest.fixture(scope="module")
def mesh8():
    devices = jax.devices("cpu")
    assert len(devices) >= 8, "conftest must force 8 virtual CPU devices"
    return mesh_mod.make_mesh(devices[:8])


@pytest.fixture(scope="module", autouse=True)
def _loop_lowering():
    # Tiny shapes on virtual CPU devices: loop lowering compiles in seconds.
    lowering.set_mode("loop")
    yield
    lowering.set_mode(None)


@pytest.fixture(scope="module")
def ecdsa_kernel(mesh8):
    # One compiled kernel shared by all tests (one shape = one compile).
    return mesh_mod.sharded_ecdsa_kernel(mesh8)


def test_sharded_ecdsa_kernel(mesh8, ecdsa_kernel):
    batch = 16  # two lanes per device
    d, q = hc.keygen()
    digest = hashlib.sha256(b"mesh-test").digest()
    sig = hc.ecdsa_sign(d, digest)
    items = [(q, digest, sig)] * batch
    items[5] = (q, digest, (sig[0], sig[1] ^ 2))  # corrupted lane
    packed = jnp.asarray(p256.prepare_packed(items, batch))

    out = np.asarray(ecdsa_kernel(packed))

    expected = np.ones(batch, dtype=bool)
    expected[5] = False
    assert (out == expected).all()


def test_sharded_hmac_kernel(mesh8):
    batch = 16
    rng = np.random.default_rng(7)
    keys = jnp.asarray(rng.integers(0, 2**32, (batch, 8), dtype=np.uint32))
    msgs = jnp.asarray(rng.integers(0, 2**32, (batch, 8), dtype=np.uint32))
    macs = hmac_sign_kernel(keys, msgs)
    kernel = mesh_mod.sharded_hmac_kernel(mesh8)
    packed = jnp.concatenate([keys, msgs, jnp.asarray(macs)], axis=1)
    assert np.asarray(kernel(packed)).all()

    bad = np.asarray(macs).copy()
    bad[3, 0] ^= 1
    packed_bad = jnp.concatenate([keys, msgs, jnp.asarray(bad)], axis=1)
    out = np.asarray(kernel(packed_bad))
    expected = np.ones(batch, dtype=bool)
    expected[3] = False
    assert (out == expected).all()


def test_sharded_output_matches_host(mesh8, ecdsa_kernel):
    """Differential check: sharded kernel agrees with the host verifier."""
    batch = 16  # same shape as test_sharded_ecdsa_kernel: no extra compile
    rng_seed = 3
    items = []
    expected = []
    for i in range(batch):
        d, q = hc.keygen()
        digest = hashlib.sha256(b"lane-%d-%d" % (rng_seed, i)).digest()
        sig = hc.ecdsa_sign(d, digest)
        if i % 4 == 1:
            sig = (sig[0], sig[1] ^ 1)
        items.append((q, digest, sig))
        expected.append(hc.ecdsa_verify(q, digest, sig))
    packed = jnp.asarray(p256.prepare_packed(items, batch))
    out = np.asarray(ecdsa_kernel(packed))
    assert out.tolist() == expected


def test_engine_routes_through_mesh(mesh8):
    """BatchVerifier(mesh=...) serves verifications through the sharded
    kernels (VERDICT r2: the serving path, not just the raw kernels):
    buckets are rounded to mesh multiples and all three schemes verify
    correctly, including rejected lanes."""
    import asyncio

    from minbft_tpu.parallel import BatchVerifier

    engine = BatchVerifier(max_batch=16, buckets=(6, 16), mesh=mesh8)
    assert engine.buckets == (8, 16)  # rounded up to mesh multiples
    assert engine.mesh is mesh8

    d, q = hc.keygen()
    digest = hashlib.sha256(b"engine-mesh").digest()
    sig = hc.ecdsa_sign(d, digest)
    seed, pub = hc.ed25519_keygen()
    ed_sig = hc.ed25519_sign(seed, b"engine-mesh")
    key = b"k" * 32
    import hmac as hmac_mod

    mac = hmac_mod.new(key, digest, hashlib.sha256).digest()

    async def run():
        ok, bad = await asyncio.gather(
            engine.verify_ecdsa_p256(q, digest, sig),
            engine.verify_ecdsa_p256(q, digest, (sig[0], sig[1] ^ 2)),
        )
        assert ok and not bad
        ok, bad = await asyncio.gather(
            engine.verify_hmac_sha256(key, digest, mac),
            engine.verify_hmac_sha256(key, digest, b"\x00" * 32),
        )
        assert ok and not bad
        ok, bad = await asyncio.gather(
            engine.verify_ed25519(pub, b"engine-mesh", ed_sig),
            engine.verify_ed25519(pub, b"other", ed_sig),
        )
        assert ok and not bad

    asyncio.run(run())
    # the sharded kernels were actually used
    assert set(engine._sharded_kernels) >= {"ecdsa", "hmac", "ed25519"}


def test_sharded_sign_kernel(mesh8):
    """Sharded fixed-base k*G agrees with the host scalar multiplication."""
    from minbft_tpu.ops.limbs import from_limbs, to_limbs
    from minbft_tpu.parallel.mesh import sharded_ecdsa_sign_kernel

    kernel = sharded_ecdsa_sign_kernel(mesh8)
    batch = 16
    rng = np.random.default_rng(11)
    ks = [int(rng.integers(1, 2**62)) for _ in range(batch)]
    k_arr = np.stack([to_limbs(k) for k in ks]).astype(np.uint32)
    xz = np.asarray(kernel(jnp.asarray(k_arr)))  # [B, 2, 16]

    r_inv = pow(1 << 256, -1, hc.P)
    for i, k in enumerate(ks):
        xm, zm = from_limbs(xz[i, 0]), from_limbs(xz[i, 1])
        assert zm != 0
        xj, zj = xm * r_inv % hc.P, zm * r_inv % hc.P
        x_aff = xj * pow(zj * zj % hc.P, -1, hc.P) % hc.P
        assert x_aff == hc.scalar_mult(k, (hc.GX, hc.GY))[0]
