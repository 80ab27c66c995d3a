"""Differential tests: batched ECDSA-P256 TPU kernel vs the host verifier
(mirrors the reference's crypto tests,
reference sample/authentication/crypto_test.go:100 — sign/verify round trip
plus forged-input rejection).

The engine's kernel sums two fixed-base combs (G's constant table, the
public key's cached table) and joins them with one complete addition; the
corpus below holds every lane to the host's verdict, the exceptional
meetings of the two combs included.  One bucket (8 lanes, the suite's
usual one) for the whole file: one compile on the CPU backend's ``loop``
lowering."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minbft_tpu.ops import limbs, p256
from minbft_tpu.ops.limbs import from_limbs
from minbft_tpu.utils import hostcrypto as hc

BUCKET = 8
G = (hc.GX, hc.GY)


@pytest.fixture(scope="module")
def keys():
    return [hc.keygen() for _ in range(3)]


def _verdicts(items, out=None):
    """Device verdicts of ``items`` through the engine's packed path, a
    bucket at a time; the last bucket's pad lanes included."""
    got = []
    for k in range(0, len(items), BUCKET):
        packed = p256.prepare_packed(items[k : k + BUCKET], BUCKET, out=out)
        got += [bool(v) for v in np.asarray(p256.ecdsa_verify_kernel_packed(packed))]
    return got


def _crafted(q_of_d, u1, u2):
    """A signature under the key d*G whose verification computes exactly
    ``u1*G + u2*Q``: R = (u1 + u2*d)*G, r = x(R) mod n, s = r/u2,
    e = u1*s (the 'digest' is e itself).  -> (Q, digest, (r, s)), or the
    same with r = 5 where R is the identity (no signature exists)."""
    d, q = q_of_d
    k = (u1 + u2 * d) % hc.N
    r = hc.scalar_mult(k, G)[0] % hc.N if k else 5
    s = r * pow(u2, -1, hc.N) % hc.N
    e = u1 * s % hc.N
    return q, e.to_bytes(32, "big"), (r, s)


def _key(d):
    return d, hc.scalar_mult(d, G)


def test_point_ops_match_host():
    f = p256.FIELD
    one = f.r_mod
    gx, gy = p256._GX_M, p256._GY_M

    def to_affine_host(pt):
        from minbft_tpu.ops.limbs import from_mont

        xi, yi, zi = (from_limbs(from_mont(f, v)) for v in pt)
        if zi == 0:
            return None
        z_inv = pow(zi, -1, hc.P)
        return (xi * z_inv**2 % hc.P, yi * z_inv**3 % hc.P)

    d2 = jax.jit(p256._dbl)(p256.Point(gx, gy, one))
    assert to_affine_host(d2) == hc.point_double((hc.GX, hc.GY))

    madd = jax.jit(lambda p, qx, qy: p256._madd(p, qx, qy, jnp.bool_(False)))
    p3, exc = madd(d2, gx, gy)
    assert to_affine_host(p3) == hc.scalar_mult(3, (hc.GX, hc.GY))
    assert not bool(exc)
    # the incomplete case P == Q is flagged ...
    g1 = p256.Point(gx, gy, one)
    _, exc = madd(g1, gx, gy)
    assert bool(exc)
    # ... and the complete addition that joins the two combs resolves it
    # through the doubling formula; it is also exact for a plain sum, for
    # inverse points, and for the identity on either side
    add = jax.jit(p256._add_complete)
    assert to_affine_host(add(g1, g1)) == hc.point_double(G)
    neg = p256.Point(gx, limbs.fe_const((-hc.GY % hc.P << 256) % hc.P), one)
    inf = p256.Point(one, one, limbs.fe_const(0))
    assert to_affine_host(add(d2, g1)) == hc.scalar_mult(3, G)
    assert to_affine_host(add(p3, p3)) == hc.scalar_mult(6, G)
    assert to_affine_host(add(g1, neg)) is None
    assert to_affine_host(add(inf, p3)) == hc.scalar_mult(3, G)
    assert to_affine_host(add(p3, inf)) == hc.scalar_mult(3, G)
    assert to_affine_host(add(inf, inf)) is None


def test_verify_batch_valid_and_forged(keys):
    items, expected = [], []
    for i, (d, q) in enumerate(keys):
        digest = hashlib.sha256(f"msg{i}".encode()).digest()
        sig = hc.ecdsa_sign(d, digest)
        assert hc.ecdsa_verify(q, digest, sig)
        items.append((q, digest, sig))
        expected.append(True)

    d0, q0 = keys[0]
    digest = hashlib.sha256(b"orig").digest()
    sig = hc.ecdsa_sign(d0, digest)
    # tampered digest
    items.append((q0, hashlib.sha256(b"tampered").digest(), sig))
    expected.append(False)
    # wrong key
    items.append((keys[1][1], digest, sig))
    expected.append(False)
    # out-of-range signature components
    items.append((q0, digest, (0, sig[1])))
    expected.append(False)
    items.append((q0, digest, (sig[0], hc.N)))
    expected.append(False)
    # bit-flipped s
    items.append((q0, digest, (sig[0], sig[1] ^ 1)))
    expected.append(False)

    got = p256.verify_batch(items)
    assert list(got) == expected


def test_is_on_curve(keys):
    _, q = keys[0]
    assert p256.is_on_curve(*q)
    assert not p256.is_on_curve(q[0], (q[1] + 1) % hc.P)
    assert not p256.is_on_curve(hc.P, 0)


# ---------------------------------------------------------------------------
# The comb kernel against the host verifier, lane by lane.


def _corpus(keys):
    """-> [(what, item)]: every case ISSUE 30 names, mixed keys in one
    batch.  The host verifier decides what is expected."""
    (d0, q0), (d1, q1), _ = keys
    digest = hashlib.sha256(b"corpus").digest()
    sig = hc.ecdsa_sign(d0, digest)
    out = [
        ("valid", (q0, digest, sig)),
        ("valid, another key", (q1, digest, hc.ecdsa_sign(d1, digest))),
        ("forged r", (q0, digest, (sig[0] ^ 4, sig[1]))),
        ("forged s", (q0, digest, (sig[0], sig[1] ^ 4))),
        ("forged digest", (q0, hashlib.sha256(b"other").digest(), sig)),
        ("r = 0", (q0, digest, (0, sig[1]))),
        ("s = n", (q0, digest, (sig[0], hc.N))),
        ("key off the curve", ((q0[0], (q0[1] + 1) % hc.P), digest, sig)),
    ]
    # u1 = 0: the digest is 0 mod n, the G comb stays at the identity
    zero = b"\x00" * 32
    out.append(("u1 = 0", (q0, zero, hc.ecdsa_sign_py(d0, zero))))
    # Q = G, Q = -G, Q = c*G: the two combs are multiples of one point
    for d in (1, hc.N - 1, 2, 3, 16, 17):
        out.append((f"Q = {d}*G", (_key(d)[1], digest, hc.ecdsa_sign_py(d, digest))))
    return out


def _corpus_meetings():
    """Crafted lanes (see :func:`_crafted`): the second x candidate, zero
    nibbles in every window of u1 or u2, and the two combs meeting in the
    complete addition."""
    k7 = _key(7)
    lo = int("0f" * 32, 16)  # nibbles 0 in every odd window
    hi = int("f0" * 31 + "e0", 16)  # ... in every even window (and < n)
    out = [
        ("u1 zero in odd windows, u2 in even", _crafted(k7, lo, hi)),
        ("u1 zero in even windows, u2 in odd", _crafted(k7, hi, lo)),
        # u1*G == u2*Q: the join is a doubling, and a VALID signature
        ("u1*G = u2*Q", _crafted(k7, 7 * 12345 % hc.N, 12345)),
        ("u1*G = u2*Q, Q = G", _crafted(_key(1), 999, 999)),
        # u1*G == -u2*Q: the sum is the identity, no signature verifies
        ("u1*G = -u2*Q", _crafted(k7, -7 * 12345 % hc.N, 12345)),
        ("u1*G = -u2*Q, Q = -G", _crafted(_key(hc.N - 1), 999, 999)),
    ]
    # r + n < p: R with x(R) in [n, p), so r = x(R) - n and only the second
    # candidate matches.  Such an x cannot be found by signing (2^-32), so
    # take the point first and fit the key to it: Q = (R - u1*G) / u2.
    x = hc.N
    while True:
        x += 1
        y2 = (x * x * x - 3 * x + p256.B) % hc.P
        y = pow(y2, (hc.P + 1) // 4, hc.P)
        if y * y % hc.P == y2:
            break
    u1, u2 = 0x1234567, 0x7654321
    neg_u1g = hc.scalar_mult(hc.N - u1, G)
    q = hc.scalar_mult(pow(u2, -1, hc.N), hc.point_add((x, y), neg_u1g))
    r = x - hc.N
    s = r * pow(u2, -1, hc.N) % hc.N
    out.append(("r + n < p", (q, (u1 * s % hc.N).to_bytes(32, "big"), (r, s))))
    # the same lane with the window closed: r2 is needed, so r alone fails
    out.append(("r + n < p, r off by one", (q, (u1 * s % hc.N).to_bytes(32, "big"), (r + 1, s))))
    return out


def test_comb_kernel_matches_host_on_the_corpus(keys):
    cases = _corpus(keys)
    want = [hc.ecdsa_verify(*item) for _, item in cases]
    # twice: first with no table but for keys met again (most lanes carry
    # u2*Q itself, a key's first use), then with every key's table built
    p256._KEY_TABLES.clear()
    for tables in ("first use", "tables"):
        got = _verdicts([item for _, item in cases])
        assert len(cases) % BUCKET and not any(got[len(cases):])  # pad lanes
        for (what, _), g, w in zip(cases, got, want):
            assert g == w, f"{what} ({tables}): device {g}, host {w}"
    assert len(p256._KEY_TABLES) >= 8
    by_name = dict(zip([w for w, _ in cases], want))
    assert by_name["valid"] and by_name["u1 = 0"] and by_name["Q = 1*G"]
    assert by_name[f"Q = {hc.N - 1}*G"] and not by_name["key off the curve"]


def test_comb_kernel_where_the_two_combs_meet():
    cases = _corpus_meetings()
    want = [hc.ecdsa_verify(*item) for _, item in cases]
    # the host accepts the doubling lanes and the second candidate, and
    # rejects the identity: the device must give ITS verdict, never an
    # `exc` rejection of a valid signature
    assert want == [True, True, True, True, False, False, True, False]
    p256.prime_key_tables([item[0] for _, item in cases])  # the comb path
    got = _verdicts([item for _, item in cases])
    for (what, _), g, w in zip(cases, got, want):
        assert g == w, f"{what}: device {g}, host {w}"
    packed = p256.prepare_packed([item for _, item in cases], BUCKET)
    assert packed[6, p256._Q_COLS + 4 * limbs.NLIMBS] == 1  # r2_ok of "r + n < p"


def test_pad_lanes_with_stale_rows_are_rejected(keys):
    """A recycled staging buffer keeps the last batch's table rows in its
    pad lanes: they are computed on and their verdict is ANDed away."""
    d, q = keys[0]
    digest = hashlib.sha256(b"stale").digest()
    valid = (q, digest, hc.ecdsa_sign(d, digest))
    buf = np.empty((BUCKET, p256.PACKED_COLS), np.uint16)
    assert _verdicts([valid] * BUCKET, out=buf) == [True] * BUCKET
    rows_before = buf[5:, : p256._Q_COLS].copy()
    got = _verdicts([valid] * 5, out=buf)
    assert np.array_equal(buf[5:, : p256._Q_COLS], rows_before)  # stale, untouched
    assert got == [True] * 5 + [False] * (BUCKET - 5)


# ---------------------------------------------------------------------------
# The per-key tables (host).


def test_comb_table_is_v_16j_q(keys):
    _, q = keys[2]
    tab = p256.comb_table(q)
    assert tab.shape == (64, 16, 32) and tab.dtype == np.uint16
    assert not tab[:, 0].any()  # infinity rows
    for j, v in [(0, 1), (0, 2), (0, 15), (1, 1), (17, 8), (40, 9), (63, 15), (63, 1)]:
        x, y = hc.scalar_mult(v * 16**j % hc.N, q)
        row = tab[j, v].astype(np.uint32)
        assert from_limbs(row[:16]) == (x << 256) % hc.P, (j, v)
        assert from_limbs(row[16:]) == (y << 256) % hc.P, (j, v)
    # G's table, as the sign and verify kernels close over it
    g = p256._COMB_TABLE_NP
    assert g.shape == (64, 16, 2, 16) and g.dtype == np.uint32
    assert np.array_equal(g.reshape(64, 16, 32), p256.comb_table(G))


def test_key_tables_lru_evicts_and_rebuilds():
    cache = p256._KeyTables(slots=2)
    qs = [hc.keygen()[1] for _ in range(3)]
    nib = np.full((1, 64), 3, np.intp)

    def rows(q, primed=True):
        tally = p256.KeyTableTally()
        if primed:
            cache.ensure([q], tally)
        got, have = cache.rows([q], nib, tally)
        assert have.all() and np.array_equal(got[0], p256.comb_table(q)[:, 3])
        return tally

    assert rows(qs[0]).builds == 1 and rows(qs[1]).builds == 1
    t = rows(qs[0])
    assert (t.hits, t.builds) == (1, 0)  # cached; now the most recent
    assert rows(qs[2]).builds == 1 and len(cache) == 2  # evicts qs[1]
    assert rows(qs[0]).builds == 0
    t = rows(qs[1])
    assert (t.hits, t.builds) == (1, 1) and t.build_s > 0  # rebuilt, then read
    # a key that is no point of the curve: no table, no slot
    tally = p256.KeyTableTally()
    bad = (qs[0][0], qs[0][1] ^ 1)
    cache.ensure([bad], tally)
    _, have = cache.rows([bad, bad, qs[1]], np.zeros((3, 64), np.intp), tally)
    assert have.tolist() == [False, False, True] and (tally.hits, tally.builds) == (1, 0)


def test_a_key_gets_its_table_on_its_second_use(keys):
    """First use: no build, the lane's one row is u2*Q itself (window 0)
    and its window scalar is 1; the second use builds the table; from then
    on every item is a hit.  The device's verdict is the host's each time."""
    p256._KEY_TABLES.clear()
    d, q = hc.keygen()
    digests = [hashlib.sha256(b"use %d" % i).digest() for i in range(5)]
    items = [(q, dg, hc.ecdsa_sign(d, dg)) for dg in digests]
    items[3] = (q, digests[3], (items[3][2][0], items[3][2][1] ^ 1))  # forged
    c, L = p256._Q_COLS, limbs.NLIMBS

    tally = p256.KeyTableTally()
    packed = p256.prepare_packed(items[:1], BUCKET, tally=tally)
    assert (tally.hits, tally.builds) == (0, 0) and len(p256._KEY_TABLES) == 0
    u2 = p256.prepare_batch(items[:1])[3][0]
    want = hc.scalar_mult(limbs.from_limbs(u2), q)
    row = packed[0, :32].astype(np.uint32)
    assert from_limbs(row[:16]) == (want[0] << 256) % hc.P
    assert from_limbs(row[16:]) == (want[1] << 256) % hc.P
    assert packed[0, c + L : c + 2 * L].tolist() == [1] + [0] * 15
    assert bool(np.asarray(p256.ecdsa_verify_kernel_packed(packed))[0])

    # the second use; two uses inside one batch count as well
    for second in (items[1:2], None):
        if second is None:
            p256._KEY_TABLES.clear()
            second = items[1:3]
        tally = p256.KeyTableTally()
        p256.prepare_packed(second, BUCKET, tally=tally)
        assert (tally.hits, tally.builds) == (0, 1) and len(p256._KEY_TABLES) == 1

    got = _verdicts(items)
    assert got[:5] == [True, True, True, False, True]


def test_prime_key_tables_then_every_item_hits(keys):
    p256._KEY_TABLES.clear()
    primed = p256.prime_key_tables([q for _, q in keys])
    assert (primed.hits, primed.builds) == (0, 3)
    digest = hashlib.sha256(b"primed").digest()
    items = [(q, digest, hc.ecdsa_sign(d, digest)) for d, q in keys] * 2
    tally = p256.KeyTableTally()
    p256.prepare_packed(items, BUCKET, tally=tally)
    assert (tally.hits, tally.builds, tally.build_s) == (6, 0, 0.0)
    assert p256.prime_key_tables([q for _, q in keys]).builds == 0


def test_what_the_benchmark_kernel_file_relies_on(keys, monkeypatch):
    """benchmark/kernels/ecdsa_verify.py finds the kernel in a trace by the
    jit's name, and its ``skip()`` (the control ``verify_skipped``) patches
    the module attribute that the engine's dispatcher reads on every call,
    with a function of ``packed`` whose ``shape[0]`` is the lanes."""
    import asyncio
    import inspect

    from minbft_tpu.parallel import BatchVerifier
    from minbft_tpu.parallel import engine as engine_mod

    assert p256._verify_one_packed.__name__ == "_verify_one_packed"
    assert p256.ecdsa_verify_kernel_packed.__name__ == "_verify_one_packed"
    lowered = jax.jit(p256.ecdsa_verify_kernel_packed).lower(
        jax.ShapeDtypeStruct((BUCKET, p256.PACKED_COLS), jnp.uint16)
    )
    assert "jit__verify_one_packed" in lowered.as_text()[:400]
    assert "p256.ecdsa_verify_kernel_packed" in inspect.getsource(
        engine_mod.BatchVerifier._dispatch_ecdsa
    )

    seen = []

    def all_valid(packed):
        seen.append(packed.shape)
        return np.ones(packed.shape[0], bool)

    monkeypatch.setattr(p256, "ecdsa_verify_kernel_packed", all_valid)
    d, q = keys[0]
    digest = hashlib.sha256(b"skip").digest()
    forged = (q, digest, (7, 9))

    async def run():
        engine = BatchVerifier(max_batch=BUCKET, buckets=(BUCKET,))
        return await engine.verify_ecdsa_p256(*forged)

    assert asyncio.run(run()) is True  # every lane "valid": the patch was read
    assert seen == [(BUCKET, p256.PACKED_COLS)]


# -- a key's first use: one host scalar multiplication ------------------------


@pytest.mark.parametrize("k", [
    1, 2, 3, 15, 16, hc.N - 1, hc.N - 2, hc.N - 3, (hc.N - 1) // 2, (hc.N + 1) // 2,
    0x8000000000000000000000000000000000000000000000000000000000000000,
    0x0A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A5A,
])
def test_point_mult_through_ecdh_equals_double_and_add(k):
    """hostcrypto.point_mult takes x(k*Q) and x((k+1)*Q) from OpenSSL's ECDH
    and solves for y: against the affine double-and-add, at both ends of
    the scalar's range (k*Q = +-Q included) and under two keys."""
    for d in (1, 0x1F2E3D4C5B6A79881726354453627180F0E1D2C3B4A5968778695A4B3C2D1E0F):
        q = hc.scalar_mult(d, (hc.GX, hc.GY))
        assert hc.point_mult(k, q) == hc.scalar_mult(k, q)


def test_point_mult_without_openssl_is_the_double_and_add(monkeypatch):
    q = hc.scalar_mult(7, (hc.GX, hc.GY))
    want = hc.point_mult(0xDEADBEEF, q)
    monkeypatch.setattr(hc, "_HAVE_OSSL", False)
    assert hc.point_mult(0xDEADBEEF, q) == want == hc.scalar_mult(0xDEADBEEF, q)


def test_a_first_use_row_is_the_point_in_the_montgomery_domain_and_is_counted(keys):
    d, q = keys[0]
    k = 0x1234567890ABCDEF1234567890ABCDEF1234567890ABCDEF1234567890ABCDEF
    row = p256._scalar_mult_row(k, q)
    assert row.shape == (32,) and row.dtype == np.uint16
    r_inv = pow(1 << 256, -1, hc.P)
    x, y = (v * r_inv % hc.P for v in limbs.from_limbs_batch(row.reshape(2, 16)))
    assert (x, y) == hc.scalar_mult(k, q)
    # in a batch: a key without a table is a first use, once a lane; a
    # primed key's lane is a hit; a key off the curve is neither
    p256._KEY_TABLES.clear()
    d2, q2 = keys[1]
    p256.prime_key_tables([q2])
    digest = hashlib.sha256(b"first use").digest()
    items = [(q, digest, hc.ecdsa_sign(d, digest)), (q2, digest, hc.ecdsa_sign(d2, digest)),
             ((q[0] ^ 1, q[1]), digest, hc.ecdsa_sign(d, digest))]
    tally = p256.KeyTableTally()
    p256.prepare_packed(items, BUCKET, tally=tally)
    assert (tally.first_uses, tally.hits, tally.builds) == (1, 1, 0)
    assert 0.0 < tally.first_use_s < 1.0
    assert _verdicts(items)[:3] == [True, True, False]
