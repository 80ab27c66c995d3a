"""Differential tests for the vectorized host batch prep (round-6).

The numpy/batch-inversion ``prepare_batch`` paths must produce
BIT-IDENTICAL packed arrays to the per-item scalar oracles
(``prepare_batch_scalar``) on random AND adversarial inputs — out-of-range
r/s, the ``r + n < p`` second-candidate edge, zero/garbage pubkeys,
non-int garbage — and the engine's recycled staging buffers must survive
``max_inflight`` concurrent dispatchers without cross-talk.
"""

import hashlib
import random
import threading

import numpy as np
import pytest

from minbft_tpu.ops import ed25519 as ed
from minbft_tpu.ops import limbs, p256
from minbft_tpu.utils import hostcrypto as hc

# ---------------------------------------------------------------------------
# limb batch helpers


def test_to_limbs_batch_matches_scalar():
    rng = random.Random(1)
    vals = [0, 1, (1 << 256) - 1, p256.P, p256.N] + [
        rng.randrange(1 << 256) for _ in range(50)
    ]
    rows = limbs.to_limbs_batch(vals)
    assert rows.dtype == np.uint32 and rows.shape == (len(vals), 16)
    for v, row in zip(vals, rows):
        assert np.array_equal(row, limbs.to_limbs(v))
    assert limbs.from_limbs_batch(rows) == vals
    assert limbs.to_limbs_batch([]).shape == (0, 16)


def test_limbs_lt_and_add_const():
    rng = random.Random(2)
    bound = p256.N
    vals = [0, 1, bound - 1, bound, bound + 1, (1 << 256) - 1] + [
        rng.randrange(1 << 256) for _ in range(100)
    ]
    rows = limbs.to_limbs_batch(vals)
    got = limbs.limbs_lt(rows, bound)
    assert list(got) == [v < bound for v in vals]
    assert list(limbs.limbs_is_zero(rows)) == [v == 0 for v in vals]
    # add_const on the no-overflow subset
    small = [v for v in vals if v + bound < (1 << 256)]
    srows = limbs.to_limbs_batch(small)
    added = limbs.limbs_add_const(srows, bound)
    assert limbs.from_limbs_batch(added) == [v + bound for v in small]


# ---------------------------------------------------------------------------
# ECDSA-P256 prep parity


def _assert_p256_parity(items):
    a = p256.prepare_batch_scalar(items)
    b = p256.prepare_batch(items)
    for i, (x, y) in enumerate(zip(a, b)):
        assert x.dtype == y.dtype, f"array {i} dtype"
        assert np.array_equal(x, y), f"array {i} diverged"
    _assert_p256_packed(items, a)


def _assert_p256_packed(items, arrays):
    """The engine's staging write against the oracle's arrays: the scalar
    columns bit for bit, ``valid`` narrowed to keys ON the curve, and for
    every valid lane the 64 rows of its key's comb table that u2's nibbles
    select.  Pad lanes: scalars and flags zero (their rows are unspecified)."""
    _qx, _qy, u1, u2, rr, r2, r2_ok, valid = arrays
    n, L, c = len(items), limbs.NLIMBS, p256._Q_COLS
    p256.prime_key_tables(  # else a key's first use carries u2*Q, not table rows
        [item[0] for item, v in zip(items, valid) if v and p256.is_on_curve(*item[0])]
    )
    packed = p256.prepare_packed(items, n + 3)
    assert packed.shape == (n + 3, p256.PACKED_COLS) and packed.dtype == np.uint16
    for k, arr in enumerate((u1, u2, rr, r2)):
        assert np.array_equal(packed[:n, c + k * L : c + (k + 1) * L], arr), f"scalar {k}"
    assert np.array_equal(packed[:n, c + 4 * L], r2_ok)
    on_curve = np.array(
        [bool(v) and p256.is_on_curve(*items[i][0]) for i, v in enumerate(valid)], bool
    )
    assert np.array_equal(packed[:n, c + 4 * L + 1], on_curve)
    for i in np.flatnonzero(on_curve):
        nib = (u2[i][:, None] >> (4 * np.arange(4, dtype=np.uint32))) & 0xF
        want = p256.comb_table(items[i][0])[np.arange(64), nib.reshape(64)]
        assert np.array_equal(packed[i, :c], want.reshape(c)), f"lane {i} rows"
    assert not packed[n:, c:].any(), "pad lanes not zeroed"


_REAL_KEYS = [hc.scalar_mult(d, (hc.GX, hc.GY)) for d in (1, 2, 0xC0FFEE)]


def _fuzz_p256_items(rng, n):
    """Mix of plausible lanes, boundary values, and garbage; one plausible
    lane in four is under a real key (a point of the curve, so it has
    comb-table rows)."""
    boundary = [
        0, 1, 2,
        p256.N - 1, p256.N, p256.N + 1,
        p256.P - 1, p256.P, p256.P + 1,
        p256.P - p256.N - 1, p256.P - p256.N, p256.P - p256.N + 1,
        (1 << 256) - 1, 1 << 256, -1, -p256.N, 1 << 300,
    ]

    def field(kind):
        if kind == 0:
            return rng.choice(boundary)
        return rng.randrange(1 << 256)

    items = []
    for _ in range(n):
        shape = rng.randrange(4)
        if shape == 0:  # plausible in-range lane
            items.append(
                (
                    rng.choice(_REAL_KEYS) if rng.randrange(4) == 0
                    else (rng.randrange(p256.P), rng.randrange(p256.P)),
                    rng.randbytes(32),
                    (rng.randrange(1, p256.N), rng.randrange(1, p256.N)),
                )
            )
        elif shape == 1:  # second-candidate window: r < p - n
            items.append(
                (
                    rng.choice(_REAL_KEYS) if rng.randrange(4) == 0
                    else (rng.randrange(p256.P), rng.randrange(p256.P)),
                    rng.randbytes(32),
                    (rng.randrange(1, p256.P - p256.N), rng.randrange(1, p256.N)),
                )
            )
        else:  # boundary/garbage components in random positions
            items.append(
                (
                    (field(rng.randrange(2)), field(rng.randrange(2))),
                    rng.randbytes(rng.choice((0, 31, 32, 33))),
                    (field(rng.randrange(2)), field(rng.randrange(2))),
                )
            )
    return items


def test_p256_prep_parity_fuzz_1000():
    """Acceptance pin: bit-identical packed arrays on >=1000 fuzzed
    inputs (random + adversarial mix, deterministic seed)."""
    rng = random.Random(0xF00D)
    items = _fuzz_p256_items(rng, 1000)
    _assert_p256_parity(items)
    # the fuzz exercises all three verdict populations
    arrays = p256.prepare_batch(items)
    valid, r2_ok = arrays[7], arrays[6]
    assert valid.any() and (~valid).any() and r2_ok.any()


def test_p256_prep_adversarial_edges():
    d, q = hc.keygen()
    digest = hashlib.sha256(b"edge").digest()
    sig = hc.ecdsa_sign(d, digest)
    items = [
        (q, digest, sig),                          # genuine
        (q, digest, (0, sig[1])),                  # r = 0
        (q, digest, (sig[0], 0)),                  # s = 0
        (q, digest, (p256.N, sig[1])),             # r = n
        (q, digest, (sig[0], p256.N)),             # s = n
        (q, digest, (p256.N - 1, p256.N - 1)),     # max in-range scalars
        (q, digest, (-1, sig[1])),                 # negative r
        (q, digest, (sig[0], 1 << 257)),           # oversized s
        ((0, 0), b"\x00" * 32, (0, 0)),            # the engine pad shape
        ((0, 0), digest, sig),                     # zero pubkey, real sig
        ((p256.P, p256.P), digest, sig),           # coords = p
        ((q[0], p256.P - 1), digest, sig),         # garbage-but-in-range y
        (q, b"", sig),                             # empty digest
        (q, digest, (7, 9)),                       # r < p - n: 2nd candidate
    ]
    _assert_p256_parity(items)
    arrays = p256.prepare_batch(items)
    valid, r2_ok = arrays[7], arrays[6]
    assert valid[0] and not valid[1] and not valid[2]
    assert not valid[3] and not valid[4] and valid[5]
    assert not valid[6] and not valid[7]
    assert r2_ok[13] and valid[13]


def test_p256_prep_scalar_oracle_roundtrip():
    """The scalar oracle and the vectorized prep agree on one signed item."""
    d, q = hc.keygen()
    digest = hashlib.sha256(b"flag").digest()
    items = [(q, digest, hc.ecdsa_sign(d, digest))]
    a = p256.prepare_batch_scalar(items)
    b = p256.prepare_batch(items)
    for x, y in zip(a, b):
        assert np.array_equal(x, y)


def test_p256_prep_empty_and_all_invalid():
    empty = p256.prepare_batch([])
    for arr, ref in zip(empty, p256.prepare_batch_scalar([])):
        assert arr.shape == ref.shape and arr.dtype == ref.dtype
    bad = [((0, 0), b"\x00" * 32, (0, 0))] * 5
    _assert_p256_parity(bad)
    assert not p256.prepare_batch(bad)[7].any()


def test_p256_prep_hypothesis_fuzz():
    """Property fuzz over prep when hypothesis is available (the bare
    jax_graft image does not ship it — the seeded fuzz above is the
    always-on floor)."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    component = st.one_of(
        st.integers(min_value=-4, max_value=1 << 257),
        st.sampled_from(
            [p256.N, p256.N - 1, p256.P, p256.P - p256.N, (1 << 256) - 1]
        ),
    )
    item = st.tuples(
        st.tuples(component, component),
        st.binary(min_size=0, max_size=40),
        st.tuples(component, component),
    )

    @settings(max_examples=50, deadline=None)
    @given(st.lists(item, min_size=1, max_size=20))
    def check(items):
        _assert_p256_parity(items)

    check()


# ---------------------------------------------------------------------------
# Ed25519 prep parity


def _assert_ed_parity(items, bucket):
    a = ed.prepare_batch_scalar(items, bucket)
    b = ed.prepare_batch(items, bucket)
    for i, (x, y) in enumerate(zip(a, b)):
        assert x.dtype == y.dtype, f"array {i} dtype"
        assert np.array_equal(x, y), f"array {i} diverged"
    assert np.array_equal(ed.pack_arrays(a), ed.prepare_packed(items, bucket))


def test_ed25519_prep_parity_fuzz():
    rng = random.Random(0xED)
    seed, pub = hc.ed25519_keygen(rng.randbytes(32))
    msgs = [rng.randbytes(rng.randrange(0, 64)) for _ in range(24)]
    items = [(pub, m, hc.ed25519_sign(seed, m)) for m in msgs]
    sig0 = items[0][2]
    items += [
        (pub, b"x", b"\x00" * 63),                                  # bad length
        (pub, b"x", b""),                                           # empty sig
        (pub, b"x", sig0[:32] + ed.L.to_bytes(32, "little")),       # s = L
        (pub, b"x", sig0[:32] + (ed.L - 1).to_bytes(32, "little")), # s = L-1
        (pub, b"x", ed.P.to_bytes(32, "little") + sig0[32:]),       # y_r = p
        (pub, b"x", (ed.P - 1).to_bytes(32, "little") + sig0[32:]), # y_r = p-1
        (pub, b"x", b"\xff" * 64),                                  # all-ones
        (b"\x00" * 32, b"y", sig0),                                 # zero pub
        (rng.randbytes(32), b"z", sig0),                            # random pub
        (pub, b"", sig0),                                           # empty msg
    ]
    # high-bit R encodings exercise the rsign split
    items += [
        (pub, b"hb", (1 << 255 | 5).to_bytes(32, "little") + sig0[32:]),
    ]
    for bucket in (len(items), len(items) + 7):
        _assert_ed_parity(items, bucket)
    valid = ed.prepare_batch(items, len(items))[6]
    assert valid[:24].all() and not valid[24] and not valid[25]


# ---------------------------------------------------------------------------
# staging-buffer reuse under concurrency


def test_staging_pool_concurrent_checkout():
    """A buffer checked out by one thread must never be handed to another
    before release — hammer acquire/hold/release from 8 threads and track
    simultaneous holders by buffer identity."""
    from minbft_tpu.parallel.engine import _StagingPool

    pool = _StagingPool()
    held: set = set()
    held_lock = threading.Lock()
    errors: list = []
    barrier = threading.Barrier(8)

    def hammer(tid):
        barrier.wait()
        for i in range(200):
            buf = pool.acquire((16, 4), np.uint16)
            with held_lock:
                if id(buf) in held:
                    errors.append(f"t{tid}: double checkout at iter {i}")
                held.add(id(buf))
            buf.fill(tid)  # scribble: a shared buffer would tear
            if not (buf == tid).all():
                errors.append(f"t{tid}: torn buffer at iter {i}")
            with held_lock:
                held.discard(id(buf))
            pool.release(buf)

    threads = [threading.Thread(target=hammer, args=(t,)) for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:5]
    # the free list is bounded by the cap, not the hammer volume
    assert sum(len(v) for v in pool._free.values()) <= pool._cap


def test_engine_staging_reuse_thread_hammer():
    """Regression for staging-buffer reuse under max_inflight concurrent
    dispatchers: distinct items through recycled buffers must produce
    their OWN verdicts (a cross-dispatch buffer share would leak lanes),
    with exact padded-lane accounting and host_prep_time_s populated."""
    import hmac as hmac_mod

    from minbft_tpu.parallel import BatchVerifier

    def item(i, valid=True):
        key = hashlib.sha256(b"key-%d" % i).digest()
        msg = hashlib.sha256(b"msg-%d" % i).digest()
        mac = hmac_mod.new(key, msg, hashlib.sha256).digest()
        if not valid:
            mac = bytes([mac[0] ^ 1]) + mac[1:]
        return key, msg, mac

    eng = BatchVerifier(max_batch=8, buckets=(8,))
    eng._queue("hmac_sha256", eng._dispatch_hmac)
    eng._dispatch_hmac([item(0)])  # warm the kernel off the clock
    base = eng.stats["hmac_sha256"].padded_lanes
    n_threads, per_thread = 8, 6
    barrier = threading.Barrier(n_threads)
    errors: list = []

    def hammer(tid):
        barrier.wait()
        for j in range(per_thread):
            i = 1000 + tid * per_thread + j
            valid = (i % 3) != 0
            batch = [item(i, valid=valid), item(i + 100000)]
            res = eng._dispatch_hmac(batch)
            if list(res) != [valid, True]:
                errors.append(f"t{tid}/{j}: {list(res)} != [{valid}, True]")

    threads = [
        threading.Thread(target=hammer, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors[:5]
    st = eng.stats["hmac_sha256"]
    assert st.padded_lanes - base == n_threads * per_thread * 6  # bucket 8, n=2
    assert st.host_prep_time_s > 0.0


# ---------------------------------------------------------------------------
# throughput acceptance (slow: excluded from the tier-1 run)


@pytest.mark.slow
def test_prep_speedup_at_least_5x():
    """Acceptance: >=5x host-prep throughput for prepare_batch at B=16384
    vs the scalar oracle on the same host (and bit-identical output on
    the same items)."""
    import time

    rng = random.Random(0x5EED)
    B = 16384
    items = [
        (
            (rng.randrange(p256.P), rng.randrange(p256.P)),
            rng.randbytes(32),
            (rng.randrange(1, p256.N), rng.randrange(1, p256.N)),
        )
        for _ in range(B)
    ]
    for vec, ref in zip(p256.prepare_batch(items), p256.prepare_batch_scalar(items)):
        assert np.array_equal(vec, ref)

    def best_of(fn, n=3):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best

    tv = best_of(lambda: p256.prepare_batch(items))
    ts = best_of(lambda: p256.prepare_batch_scalar(items))
    assert ts / tv >= 5.0, f"speedup {ts / tv:.2f}x < 5x ({tv:.3f}s vs {ts:.3f}s)"
