"""Batched ECDSA-P256 verification (and signing) as JAX/XLA TPU kernels.

This is the north-star hot path: the reference verifies every PREPARE/COMMIT
UI certificate and client signature serially on CPU (Go crypto/ecdsa at
sample/authentication/crypto.go:79-89; enclave-side create at
usig/sgx/enclave/usig.c:36-76, verification in pure Go at
usig/sgx/sgx-usig.go:81-97).  Here a whole batch of verifications runs as one
data-parallel XLA program whose field arithmetic is the fused limb machinery
of :mod:`minbft_tpu.ops.limbs`.

Division of labor (TPU-first):

- **Host, once per public key**: a fixed-base comb table of the key,
  ``T[j][v] = v * 16^j * Q`` (:func:`comb_table`, ~10 ms, 64 KiB), cached
  by key in a bounded LRU (:class:`_KeyTables`).  A deployment's keys are
  few and stable — the clients, replicas and USIG identities of one key
  store — and :func:`prime_key_tables` builds them before a replica serves;
  any other key is served once by a host scalar multiplication (~0.3 ms)
  and gets its table when it comes back.
- **Host, per batch**: hashes variable-length bytes to the fixed 32-byte
  digest ``z`` (:func:`minbft_tpu.messages.authen_digest`), computes
  ``u1 = z*s^-1 mod n`` and ``u2 = r*s^-1 mod n`` with native big ints —
  ONE Montgomery batch inversion per batch, which keeps mod-n arithmetic
  off the device — range-checks the batch with whole-batch numpy limb
  compares, and selects with one fancy-index the 64 table rows that each
  lane's u2 names (:func:`prepare_packed`).
- **Device** adds: ``u1*G + u2*Q`` as two combs of 64 mixed additions (G's
  table is a constant of the executable), one complete addition to join
  them, and the affine-free final check ``X == r * Z^2`` — no doubling, no
  inversion, constant shape, jit-compiled once per batch bucket
  (:func:`_verify_one_packed`).

Adversarial-input policy: the mixed-addition formula is incomplete (it
cannot add a point to itself).  Inside one comb that case cannot arise for
scalars below n; the kernel still *detects* it and marks the lane rejected
(``exc`` flag), which is always sound — the kernel only ever errs toward
rejection.  Where the two combs join, every case (equal points, inverse
points, the identity on either side) is handled exactly with constant-shape
selects, so a valid signature under ANY key of the curve — Q = G, Q = -G,
Q = c*G — gets the host verifier's verdict.  A key that is not a point of
the curve is refused on the host, as OpenSSL refuses it at load.
"""

from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import NamedTuple, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import limbs
from .limbs import (
    Fe,
    FieldSpec,
    add_mod,
    fe_const,
    fe_eq,
    fe_from_array,
    fe_is_zero,
    fe_select,
    mont_mul,
    mont_one,
    mont_sqr,
    sub_mod,
    to_limbs,
)
from .lowering import per_mode_jit

# ---------------------------------------------------------------------------
# Curve constants (NIST P-256 / secp256r1, FIPS 186-4 D.1.2.3).

P = 0xFFFFFFFF00000001000000000000000000000000FFFFFFFFFFFFFFFFFFFFFFFF
N = 0xFFFFFFFF00000000FFFFFFFFFFFFFFFFBCE6FAADA7179E84F3B9CAC2FC632551
B = 0x5AC635D8AA3A93E7B3EBBD55769886BC651D06B0CC53B0F63BCE3C3E27D2604B
GX = 0x6B17D1F2E12C4247F8BCE6E563A440F277037D812DEB33A0F4A13945D898C296
GY = 0x4FE342E2FE1A7F9B8EE7EB4A7C0F9E162BCE33576B315ECECBB6406837BF51F5

FIELD = FieldSpec.make(P)
ORDER = FieldSpec.make(N)

_GX_M = fe_const((GX << 256) % P)  # Montgomery-domain constants
_GY_M = fe_const((GY << 256) % P)


class Point(NamedTuple):
    """Jacobian point, coordinates in Montgomery domain. Z == 0 <=> identity."""

    x: Fe
    y: Fe
    z: Fe


def _dbl(p: Point) -> Point:
    """Jacobian doubling, a = -3 (dbl-2001-b).  Maps identity to identity."""
    f = FIELD
    delta = mont_sqr(f, p.z)
    gamma = mont_sqr(f, p.y)
    beta = mont_mul(f, p.x, gamma)
    t0 = sub_mod(f, p.x, delta)
    t1 = add_mod(f, p.x, delta)
    alpha = mont_mul(f, add_mod(f, add_mod(f, t0, t0), t0), t1)  # 3(x-d)(x+d)
    beta4 = add_mod(f, add_mod(f, beta, beta), add_mod(f, beta, beta))
    beta8 = add_mod(f, beta4, beta4)
    x3 = sub_mod(f, mont_sqr(f, alpha), beta8)
    yz = add_mod(f, p.y, p.z)
    z3 = sub_mod(f, sub_mod(f, mont_sqr(f, yz), gamma), delta)
    g2 = mont_sqr(f, gamma)
    g8 = add_mod(f, add_mod(f, g2, g2), add_mod(f, g2, g2))
    g8 = add_mod(f, g8, g8)
    y3 = sub_mod(f, mont_mul(f, alpha, sub_mod(f, beta4, x3)), g8)
    return Point(x3, y3, z3)


def _madd(
    p: Point, qx: Fe, qy: Fe, q_inf: jnp.ndarray
) -> Tuple[Point, jnp.ndarray]:
    """Mixed Jacobian + affine addition (madd, 8M+3S).

    Returns (result, exc) where ``exc`` flags the formula's undefined case
    p == q (same x, same y, both finite) — callers must reject the lane.
    p == -q falls out correctly as the identity (Z3 = Z1*H = 0); identity
    operands are resolved by selects.
    """
    x1, y1, z1 = p
    f = FIELD
    z1z1 = mont_sqr(f, z1)
    u2 = mont_mul(f, qx, z1z1)
    s2 = mont_mul(f, qy, mont_mul(f, z1, z1z1))
    h = sub_mod(f, u2, x1)
    r = sub_mod(f, s2, y1)
    hh = mont_sqr(f, h)
    hhh = mont_mul(f, h, hh)
    v = mont_mul(f, x1, hh)
    x3 = sub_mod(f, sub_mod(f, mont_sqr(f, r), hhh), add_mod(f, v, v))
    y3 = sub_mod(f, mont_mul(f, r, sub_mod(f, v, x3)), mont_mul(f, y1, hhh))
    z3 = mont_mul(f, z1, h)

    p_inf = fe_is_zero(z1)
    exc = fe_is_zero(h) & fe_is_zero(r) & ~p_inf & ~q_inf

    one = mont_one(f)
    zero = limbs.fe_zero()
    # p identity -> q (affine lift); q identity -> p; both -> identity.
    x3 = fe_select(p_inf, qx, fe_select(q_inf, x1, x3))
    y3 = fe_select(p_inf, qy, fe_select(q_inf, y1, y3))
    z3 = fe_select(
        p_inf, fe_select(q_inf, zero, one), fe_select(q_inf, z1, z3)
    )
    return Point(x3, y3, z3), exc


def _add_complete(p: Point, q: Point) -> Point:
    """Complete Jacobian + Jacobian addition (add-1998-cmo-2, 12M+4S, and
    one ``_dbl`` selected in for p == q).  Every case exact: p == -q falls
    out as the identity (Z3 = Z1*Z2*H = 0), identity operands are resolved
    by selects.  Used ONCE per verify, to join the two comb sums — the one
    place where a multiple of G can meet a multiple of Q."""
    f = FIELD
    z1z1 = mont_sqr(f, p.z)
    z2z2 = mont_sqr(f, q.z)
    u1 = mont_mul(f, p.x, z2z2)
    u2 = mont_mul(f, q.x, z1z1)
    s1 = mont_mul(f, p.y, mont_mul(f, q.z, z2z2))
    s2 = mont_mul(f, q.y, mont_mul(f, p.z, z1z1))
    h = sub_mod(f, u2, u1)
    r = sub_mod(f, s2, s1)
    hh = mont_sqr(f, h)
    hhh = mont_mul(f, h, hh)
    v = mont_mul(f, u1, hh)
    x3 = sub_mod(f, sub_mod(f, mont_sqr(f, r), hhh), add_mod(f, v, v))
    y3 = sub_mod(f, mont_mul(f, r, sub_mod(f, v, x3)), mont_mul(f, s1, hhh))
    z3 = mont_mul(f, mont_mul(f, p.z, q.z), h)

    p_inf = fe_is_zero(p.z)
    q_inf = fe_is_zero(q.z)
    same = fe_is_zero(h) & fe_is_zero(r) & ~p_inf & ~q_inf
    d = _dbl(p)

    def pick(a, b, c, dd):  # the sum's, p's, q's, the doubling's coordinate
        return fe_select(
            p_inf, c, fe_select(q_inf, b, fe_select(same, dd, a))
        )

    return Point(
        pick(x3, p.x, q.x, d.x), pick(y3, p.y, q.y, d.y), pick(z3, p.z, q.z, d.z)
    )


# ---------------------------------------------------------------------------
# Host-side batch preparation.
#
# Division of labor for the batch-inversion prep (round-6): the device
# kernels were already fast enough that a 16384-lane batch was fed by a
# SERIAL host loop doing one ~25us ``pow(s, -1, N)`` and six per-item
# ``to_limbs`` list comprehensions per lane — the classic host-bound input
# pipeline.  The vectorized ``prepare_batch`` below replaces that with
#
# - ONE modular inversion per batch: Montgomery batch inversion
#   (:func:`minbft_tpu.ops.limbs.batch_inv_host` prefix-product sweep) —
#   3 cheap big-int multiplies per item instead of a pow each;
# - whole-batch limb packing: ints -> 32-byte little-endian -> one
#   ``np.frombuffer`` as [B, 16] '<u2' (:func:`limbs.to_limbs_batch`);
# - range validity (r, s in [1, n-1], coordinates < p, the r + n < p
#   second-candidate window) as vectorized limb comparisons
#   (:func:`limbs.limbs_lt`) feeding the kernel's ``valid`` lanes.
#
# ``prepare_batch_scalar`` keeps the original per-item path bit-for-bit as
# the differential oracle (tests assert packed-array identity).

_ZERO128 = b"\x00" * 128  # one all-zero packed record (r | s | x | y)
_N_WORDS = limbs.words_of(N)
_P_WORDS = limbs.words_of(P)
_PN_WORDS = limbs.words_of(P - N)  # r + n < p  <=>  r < p - n


def prepare_batch_scalar(
    items: Sequence[Tuple[Tuple[int, int], bytes, Tuple[int, int]]],
) -> Tuple[np.ndarray, ...]:
    """Per-item reference prep: one ``pow(s, -1, N)`` and six ``to_limbs``
    per lane.  The differential ORACLE for the vectorized
    :func:`prepare_batch`, kept verbatim."""
    b = len(items)
    qx = np.zeros((b, limbs.NLIMBS), np.uint32)
    qy = np.zeros((b, limbs.NLIMBS), np.uint32)
    u1 = np.zeros((b, limbs.NLIMBS), np.uint32)
    u2 = np.zeros((b, limbs.NLIMBS), np.uint32)
    rr = np.zeros((b, limbs.NLIMBS), np.uint32)
    r2 = np.zeros((b, limbs.NLIMBS), np.uint32)
    r2_ok = np.zeros((b,), np.bool_)
    valid = np.zeros((b,), np.bool_)
    for i, ((x, y), digest, (r, s)) in enumerate(items):
        if not (0 < r < N and 0 < s < N and 0 <= x < P and 0 <= y < P):
            continue
        z = int.from_bytes(digest[:32], "big") % N
        w = pow(s, -1, N)
        qx[i] = to_limbs(x)
        qy[i] = to_limbs(y)
        u1[i] = to_limbs((z * w) % N)
        u2[i] = to_limbs((r * w) % N)
        rr[i] = to_limbs(r)
        if r + N < P:
            r2[i] = to_limbs(r + N)
            r2_ok[i] = True
        valid[i] = True
    return qx, qy, u1, u2, rr, r2, r2_ok, valid


def prepare_batch(
    items: Sequence[Tuple[Tuple[int, int], bytes, Tuple[int, int]]],
) -> Tuple[np.ndarray, ...]:
    """[(pubkey (x, y), digest32, (r, s))] -> device-ready limb arrays.

    Host computes w = s^-1 mod n (ONE batch inversion for the whole
    batch), u1 = z*w, u2 = r*w (mod n) with Python big ints, and packs /
    range-checks the batch with vectorized numpy (see the section note
    above).  Out-of-range signatures get valid=False and all-zero lanes so
    the batch shape never changes.  Bit-identical to
    :func:`prepare_batch_scalar`.
    """
    b = len(items)
    nl = limbs.NLIMBS
    if b == 0:
        z16 = np.zeros((0, nl), np.uint32)
        zb = np.zeros((0,), np.bool_)
        return z16, z16, z16, z16, z16, z16, zb, zb

    # Pass 1 (per item, C-level): ints -> little-endian bytes.  Values
    # outside [0, 2^256) cannot pack (to_bytes raises) — their lane is
    # invalid regardless of the curve-order checks below, so pack zeros
    # and mark unfit.
    buf = bytearray()
    unfit = []
    for i, ((x, y), _digest, (r, s)) in enumerate(items):
        try:
            rec = (
                r.to_bytes(32, "little")
                + s.to_bytes(32, "little")
                + x.to_bytes(32, "little")
                + y.to_bytes(32, "little")
            )
        except (OverflowError, TypeError, AttributeError):
            rec = _ZERO128
            unfit.append(i)
        buf += rec
    raw = bytes(buf)
    rows = np.frombuffer(raw, dtype="<u2").reshape(b, 4, nl)
    words = np.frombuffer(raw, dtype="<u8").reshape(b, 4, 4)
    rw, sw = words[:, 0], words[:, 1]

    # Vectorized range validity: r, s in [1, n-1]; coordinates < p.
    valid = (
        rw.any(axis=1)
        & limbs.words_lt(rw, _N_WORDS)
        & sw.any(axis=1)
        & limbs.words_lt(sw, _N_WORDS)
        & limbs.words_lt(words[:, 2], _P_WORDS)
        & limbs.words_lt(words[:, 3], _P_WORDS)
    )
    if unfit:
        valid[unfit] = False

    # Pass 2 (valid lanes only): ONE inversion for the batch, then 2
    # multiplies per lane for the scalars.
    all_valid = bool(valid.all())
    idx = range(b) if all_valid else np.flatnonzero(valid).tolist()
    ws = limbs.batch_inv_host([items[i][2][1] for i in idx], N)
    u1_ints, u2_ints = [], []
    for i, w in zip(idx, ws):
        (_xy, digest, (r, _s)) = items[i]
        z = int.from_bytes(digest[:32], "big") % N
        u1_ints.append(z * w % N)
        u2_ints.append(r * w % N)
    if all_valid:
        u1 = limbs.to_limbs_batch(u1_ints)
        u2 = limbs.to_limbs_batch(u2_ints)
    else:
        u1 = np.zeros((b, nl), np.uint32)
        u2 = np.zeros((b, nl), np.uint32)
        if idx:
            u1[idx] = limbs.to_limbs_batch(u1_ints)
            u2[idx] = limbs.to_limbs_batch(u2_ints)

    # Second x-candidate: r + n < p  <=>  r < p - n, so the window check
    # needs no addition; the candidate itself is a vectorized limb add
    # computed only over the (rare: r < ~2^224) lanes inside the window —
    # no overflow there since r + n < p < 2^256.
    r2_ok = valid & limbs.words_lt(rw, _PN_WORDS)
    r2 = np.zeros((b, nl), np.uint32)
    i2 = np.flatnonzero(r2_ok)
    if len(i2):
        r2[i2] = limbs.limbs_add_const(rows[i2, 0], N)

    # Invalid lanes are all-zero in the oracle (its loop skips them
    # before writing) — mask for bit-identical output.
    if all_valid:
        qx = rows[:, 2].astype(np.uint32)
        qy = rows[:, 3].astype(np.uint32)
        rr = rows[:, 0].astype(np.uint32)
    else:
        lane = valid[:, None]
        z16 = np.uint16(0)
        qx = np.where(lane, rows[:, 2], z16).astype(np.uint32)
        qy = np.where(lane, rows[:, 3], z16).astype(np.uint32)
        rr = np.where(lane, rows[:, 0], z16).astype(np.uint32)
    return qx, qy, u1, u2, rr, r2, r2_ok, valid


# ---------------------------------------------------------------------------
# Fixed-base comb tables (host).
#
# Write a scalar k = sum_j k_j * 16^j over 64 nibble windows and
# precompute T[j][v] = v * 16^j * B (affine, Montgomery domain) ON THE HOST
# for a base point B: then k*B = sum_j T[j][k_j] is 64 mixed additions and
# NO doubling (~7x fewer field multiplications than a 256-step
# double-and-add ladder).  Signing uses it with B = G (one table, a
# compile-time constant of the sign kernel).  Verification uses it TWICE:
# u1*G against G's table and u2*Q against a table built once per public
# key — the key set of a deployment is small and stable (clients, replicas
# and USIG identities of one key store), so the table is cached by key and
# the device never sees Q itself, only the 64 rows of its table that the
# lane's u2 selects.
#
# What was measured before, and what runs now.  Until PR 30 verification
# was an interleaved Shamir ladder (256 doublings, 256 unconditional mixed
# additions against {Q, G, G+Q}, one Fermat inversion for the G+Q entry:
# ~5,400 field multiplications a lane, 9.56 ms a 512-lane dispatch on a
# v5e).  Round 3 had tried windowing it and recorded a dead end: signed
# windows (w = 4, 5) with host tables for G but per-lane Jacobian tables
# of Q BUILT ON THE DEVICE, at batch 4,096 / 16,384 (the throughput
# regime), were slower (77-86k against 110-113k verifies/s) and compiled
# 2-4 times longer — the windowed bodies kept the 256 doublings and held
# 9-17 table entries a lane live across the loop — and per-lane dynamic
# gathers for the lookups were 6 times worse still.  None of that is what
# runs here: no doubling at all, no table on the device but G's constant
# one, the per-lane rows selected on the host and uploaded as plain input
# (~2 MB a 512-lane dispatch), and the regime is latency at 512 lanes.

_COMB_WINDOWS = 64
_COMB_ROW = 2 * limbs.NLIMBS  # one affine point: x limbs | y limbs


def _jac_dbl_host(pt):
    """Jacobian doubling mod p, a = -3, Python ints (table building)."""
    x, y, z = pt
    delta = z * z % P
    gamma = y * y % P
    beta = x * gamma % P
    alpha = 3 * (x - delta) * (x + delta) % P
    x3 = (alpha * alpha - 8 * beta) % P
    z3 = ((y + z) * (y + z) - gamma - delta) % P
    y3 = (alpha * (4 * beta - x3) - 8 * gamma * gamma) % P
    return x3, y3, z3


def _jac_add_host(p1, p2):
    """Jacobian addition mod p of two finite points with p1 != +-p2 (the
    only additions a comb table of a point of prime order needs)."""
    x1, y1, z1 = p1
    x2, y2, z2 = p2
    z1z1 = z1 * z1 % P
    z2z2 = z2 * z2 % P
    u1 = x1 * z2z2 % P
    u2 = x2 * z1z1 % P
    s1 = y1 * z2 * z2z2 % P
    s2 = y2 * z1 * z1z1 % P
    h = (u2 - u1) % P
    r = (s2 - s1) % P
    hh = h * h % P
    hhh = h * hh % P
    v = u1 * hh % P
    x3 = (r * r - hhh - 2 * v) % P
    y3 = (r * (v - x3) - s1 * hhh) % P
    return x3, y3, z1 * z2 * h % P


def _small_multiples_host(base):
    """[1*base, ..., 15*base], Jacobian: even multiples by doubling (8
    multiplications against an addition's 16).  ``base`` finite, of order
    n: no sum meets its addend."""
    row = [base, _jac_dbl_host(base)]
    for v in range(3, 16):
        row.append(
            _jac_add_host(row[-1], base) if v & 1 else _jac_dbl_host(row[v // 2 - 1])
        )
    return row


def _affine_mont_host(jac) -> list:
    """Jacobian points -> [x0, y0, x1, y1, ...] affine, Montgomery domain,
    with ONE inversion for all of them (:func:`limbs.batch_inv_host`)."""
    z_inv = limbs.batch_inv_host([z for _, _, z in jac], P)
    r_mont = (1 << 256) % P
    coords = []
    for (x, y, _), zi in zip(jac, z_inv):
        zi2 = zi * zi % P
        coords.append(x * zi2 % P * r_mont % P)
        coords.append(y * zi2 % P * zi % P * r_mont % P)
    return coords


def comb_table(point: Tuple[int, int]) -> np.ndarray:
    """Affine (x, y) ON the curve -> [64, 16, 32] u16 comb table:
    ``T[j, v] = x limbs | y limbs`` of ``v * 16^j * point`` in the
    Montgomery domain; the v = 0 rows (infinity) are zeros and are skipped
    by the nibble, as the sign kernel skips them.

    Jacobian sums in Python integers and one batch inversion for the 960
    entries: ~10 ms a key, 64 KiB.  The curve has prime order, so every
    finite point has order n."""
    base = (point[0], point[1], 1)
    jac = []
    for _ in range(_COMB_WINDOWS):
        row = _small_multiples_host(base)
        jac.extend(row)
        base = _jac_dbl_host(row[7])  # 16 * base = 2 * (8 * base)
    tab = np.zeros((_COMB_WINDOWS, 16, _COMB_ROW), np.uint16)
    tab[:, 1:] = limbs.to_limbs_batch(_affine_mont_host(jac)).reshape(
        _COMB_WINDOWS, 15, _COMB_ROW
    )
    return tab


def _scalar_mult_row(k: int, point: Tuple[int, int]) -> np.ndarray:
    """``k * point`` (0 < k < n, point ON the curve) as one table row, [32]
    u16, Montgomery domain: one host scalar multiplication
    (:func:`hostcrypto.point_mult`, ~0.3 ms through OpenSSL).  For the first
    use of a key that has no comb table (see :class:`_KeyTables`)."""
    from ..utils import hostcrypto as hc

    x, y = hc.point_mult(k, point)
    r_mont = (1 << 256) % P
    return limbs.to_limbs_batch([x * r_mont % P, y * r_mont % P]).reshape(_COMB_ROW).astype(np.uint16)


# G's comb table as the kernels close over it: [64, 16, 2, NLIMBS] u32.
# Built at import (~15 ms), not on first use: the verify kernel reads it
# while it is traced.
_COMB_TABLE_NP = (
    comb_table((GX, GY)).reshape(_COMB_WINDOWS, 16, 2, limbs.NLIMBS).astype(np.uint32)
)


@dataclasses.dataclass
class KeyTableTally:
    """What one or more :func:`prepare_packed` calls did with the key
    tables: ``hits`` items whose key's table was cached, ``builds`` tables
    built (by priming, or inside the call that met a key for the second
    time) and the seconds they took, ``first_uses`` items served by one
    host scalar multiplication (a key's first use) and their seconds."""

    hits: int = 0
    builds: int = 0
    build_s: float = 0.0
    first_uses: int = 0
    first_use_s: float = 0.0


class _KeyTables:
    """Comb tables by public key: a bounded LRU over one preallocated
    array, so that a batch's rows are ONE fancy-index whatever its mix of
    keys.  ``slots`` tables of 64 KiB: 64 MiB at the worst (untouched
    slots are never paged in; a 30-key deployment holds under 2 MiB).  A
    key met past that evicts the least recently used and is built again
    when it returns; ``KeyTableTally.builds`` shows it.

    A key gets its table when it is primed (:meth:`ensure`) or on its
    SECOND use; its first use is served by one scalar multiplication on
    the host (:func:`prepare_packed`), ~0.3 ms against a build's ~10 ms.
    So a key that is used once (an engine's warm-up item, a calibration
    dispatch, a probe) costs no build and no slot, and a table is paid for
    only by a key that comes back.

    Which keys come here is the key store's to say, not a peer's: the
    authenticator verifies a client or replica signature under the store's
    key for the claimed id and a UI under the store's USIG anchor (only
    the epoch is pinned on first use), and refuses an unknown id before
    any verification (sample/authentication/authenticator.py).  So builds
    are bounded by the store, and :func:`prime_key_tables` does them
    before a replica serves."""

    def __init__(self, slots: int = 1024):
        self._tables = np.empty((slots, _COMB_WINDOWS, 16, _COMB_ROW), np.uint16)
        self._rows = self._tables.reshape(-1, _COMB_ROW)  # a view: one row an index
        self._slot: "collections.OrderedDict[Tuple[int, int], int]" = (
            collections.OrderedDict()
        )
        self._used_once: "collections.OrderedDict[Tuple[int, int], None]" = (
            collections.OrderedDict()
        )
        # Serialises slot assignment AND the gather: a slot must not be
        # recycled under a reader.  Builds run outside it.
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._slot)

    def clear(self) -> None:
        with self._lock:
            self._slot.clear()
            self._used_once.clear()

    def ensure(self, keys, tally: KeyTableTally) -> None:
        """Build the table of every key of ``keys`` that has none and is a
        point of the curve."""
        for key in set(keys).difference(self._slot):
            if not is_on_curve(*key):
                continue
            t0 = time.perf_counter()
            table = comb_table(key)
            with self._lock:
                if key not in self._slot:  # else another thread built it meanwhile
                    if len(self._slot) < len(self._tables):
                        slot = len(self._slot)
                    else:
                        _, slot = self._slot.popitem(last=False)
                    self._tables[slot] = table
                    self._slot[key] = slot
            tally.builds += 1
            tally.build_s += time.perf_counter() - t0

    def rows(self, keys, nibbles: np.ndarray, tally: KeyTableTally):
        """``keys``: m public keys; ``nibbles``: [m, 64] window values of
        their lanes' u2.  -> ([m, 64, 32] u16 table rows, [m] bool: False
        where the key has no table, its rows then being arbitrary)."""
        lacking = collections.Counter(k for k in keys if k not in self._slot)
        tally.hits += len(keys) - sum(lacking.values())
        if lacking:
            with self._lock:
                again = [k for k, n in lacking.items() if n > 1 or k in self._used_once]
                self._used_once.update(dict.fromkeys(lacking))
                while len(self._used_once) > len(self._tables):
                    self._used_once.popitem(last=False)
            self.ensure(again, tally)
        with self._lock:
            slots = list(map(self._slot.get, keys))
            if len(self._slot) == len(self._tables):  # full: keep LRU order
                for key in set(keys).intersection(self._slot):
                    self._slot.move_to_end(key)
            have = np.array([slot is not None for slot in slots], np.bool_)
            at = np.array([slot or 0 for slot in slots], np.intp)
            index = (at[:, None] * _COMB_WINDOWS + _WINDOW_INDEX) * 16 + nibbles
            got = self._rows.take(index.ravel(), axis=0)
        return got.reshape(len(keys), _COMB_WINDOWS, _COMB_ROW), have


_WINDOW_INDEX = np.arange(_COMB_WINDOWS)
_KEY_TABLES = _KeyTables()


def prime_key_tables(keys) -> KeyTableTally:
    """Build the comb tables of ``keys`` — the P-256 points a key store
    names — so that no build falls inside a served request."""
    tally = KeyTableTally()
    _KEY_TABLES.ensure([tuple(k) for k in keys], tally)
    return tally


# ---------------------------------------------------------------------------
# Packed verification: the engine's path.
#
# One u16 row per lane (limb values are 16-bit by construction, flags are
# 0/1), one host->device transfer per dispatch:
#
#   [0, 2048)     the 64 rows of Q's comb table that u2's nibbles select
#   [2048, 2064)  u1   (the device selects G's rows itself, from the
#                       constant table, as the sign kernel does)
#   [2064, 2080)  u2   (its nibbles flag the infinity rows; 1 where the
#                       lane's one row is u2*Q itself: a key's first use)
#   [2080, 2096)  r
#   [2096, 2112)  r2 = r + n, meaningful where r2_ok
#   2112, 2113    r2_ok, valid

_Q_COLS = _COMB_WINDOWS * _COMB_ROW
PACKED_COLS = _Q_COLS + 4 * limbs.NLIMBS + 2
_NIBBLE_SHIFTS = 4 * np.arange(4, dtype=np.uint32)  # limb i holds windows 4i..4i+3


def prepare_packed(
    items: Sequence[Tuple[Tuple[int, int], bytes, Tuple[int, int]]],
    bucket: int,
    out: "np.ndarray | None" = None,
    tally: "KeyTableTally | None" = None,
) -> np.ndarray:
    """:func:`prepare_batch` (range checks, ONE batch inversion, u1, u2)
    plus the table rows, as one [bucket, PACKED_COLS] u16 staging write.
    ``out`` (engine-owned staging buffer, recycled across dispatches) is
    written in place when given.  Lanes past ``len(items)`` and lanes
    whose signature or key is out of range get ``valid = 0``; their table
    rows are whatever the buffer held (the kernel computes on them and
    ANDs the verdict away).  A key that is not a point of the curve makes
    its lane invalid here, on the host — OpenSSL's verdict at key load.
    A lane whose key has no table yet (its first use) carries u2*Q itself
    as its one row.  ``tally`` (optional) is told the key tables' hits
    and builds."""
    n = len(items)
    out = limbs.staging_out(out, bucket, PACKED_COLS, n)
    _qx, _qy, u1, u2, rr, r2, r2_ok, valid = prepare_batch(items)
    L = limbs.NLIMBS
    idx = np.flatnonzero(valid)
    if len(idx):
        keys = [(items[i][0][0], items[i][0][1]) for i in idx]
        nib = ((u2[idx][:, :, None] >> _NIBBLE_SHIFTS) & 0xF).reshape(
            len(idx), _COMB_WINDOWS
        )
        tally = tally if tally is not None else KeyTableTally()
        got, have = _KEY_TABLES.rows(keys, nib, tally)
        for k in np.flatnonzero(~have):  # a key without a table
            lane = idx[k]
            if not is_on_curve(*keys[k]):
                valid[lane] = False
                continue
            # First use of the key: its one row is u2*Q itself, in window
            # 0, and the scalar the kernel reads the windows from is 1.
            t0 = time.perf_counter()
            got[k, 0] = _scalar_mult_row(limbs.from_limbs_batch(u2[lane : lane + 1])[0], keys[k])
            u2[lane] = 0
            u2[lane, 0] = 1
            tally.first_uses += 1
            tally.first_use_s += time.perf_counter() - t0
        out[idx, :_Q_COLS] = got.reshape(len(idx), _Q_COLS)
    c = _Q_COLS
    out[:n, c : c + L] = u1
    out[:n, c + L : c + 2 * L] = u2
    out[:n, c + 2 * L : c + 3 * L] = rr
    out[:n, c + 3 * L : c + 4 * L] = r2
    out[:n, c + 4 * L] = r2_ok
    out[:n, c + 4 * L + 1] = valid
    out[n:, c:] = 0
    return out


def _nibbles_of(scalar_arr: jnp.ndarray) -> jnp.ndarray:
    """[16] u32 limb array -> [64] nibble windows, window j = bits 4j..4j+3."""
    shifts = jnp.asarray(_NIBBLE_SHIFTS)[None, :]
    return ((scalar_arr[:, None] >> shifts) & 0xF).reshape(_COMB_WINDOWS)


def _select_row(tab_j: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    """Row ``v`` of one window's [16, 2, L] constant table by an elementwise
    masked sum — no gather, nothing per-lane resident across the loop."""
    mask = (jnp.arange(16, dtype=jnp.uint32) == v)[:, None, None]
    return jnp.sum(jnp.where(mask, tab_j, 0), axis=0)  # [2, L]


def _verify_one_packed(packed: jnp.ndarray) -> jnp.ndarray:
    """ECDSA verify of ONE dispatch: [B, PACKED_COLS] u16 (layout above)
    -> [B] bool.  (The name is the jit's in a profiler trace,
    ``jit__verify_one_packed``, which the benchmark finds the kernel by.)

    Two fixed-base combs, no doubling, no inversion: ``u1*G`` sums the
    rows of G's constant table that u1's nibbles select (on the device, a
    masked sum, as the sign kernel does) and ``u2*Q`` the rows of the
    key's table that the HOST selected by u2's nibbles.  Both run as ONE
    chain of 64 ``_madd`` over 2B chain-lanes — lanes [0, B) carry the G
    comb, [B, 2B) the Q comb — because a limb vector of 512 lanes fills
    half a vector register (XLA tiles it ``T(512)``): a second chain of
    512 beside the first costs no further instruction, and the loop body
    holds one ``_madd``, not two.  So the batch axis is explicit here, not
    ``vmap``'s.  Then ONE complete addition joins the halves, and the
    affine-free check is ``X == r * Z^2`` without an inversion: x(R) =
    X/Z^2, against both candidates r and r2 = r + n (where ``r2_ok``).

    Exceptional cases: inside one comb a partial sum m*B, m < 16^(j+1),
    cannot be +-(the next addend k * 16^(j+1) * B) for scalars below n and
    B of order n, so ``_madd``'s ``exc`` cannot fire; it is OR-folded into
    a rejection all the same.  The two combs are multiples of DIFFERENT
    points and can meet (Q = c*G): they are summed apart and joined by
    :func:`_add_complete`, exact for a1 = +-a2 and for either being the
    identity (u1 = 0).  ``valid`` carries the host's range checks; invalid
    and pad lanes burn the same cycles and return False."""
    f = FIELD
    B, L, c = packed.shape[0], limbs.NLIMBS, _Q_COLS
    p32 = packed.astype(jnp.uint32)
    q_rows = p32[:, :c].reshape(B, _COMB_WINDOWS, 2, L)
    scalars = p32[:, c : c + 4 * L].reshape(B, 4, L)  # u1, u2, r, r2
    r2_ok = p32[:, c + 4 * L] != 0
    valid = p32[:, c + 4 * L + 1] != 0
    nibbles = jax.vmap(_nibbles_of)
    nib = jnp.concatenate([nibbles(scalars[:, 0]), nibbles(scalars[:, 1])])
    g_table = jnp.asarray(_COMB_TABLE_NP)
    select_rows = jax.vmap(_select_row, in_axes=(None, 0))

    def body(j, carry):
        acc, exc = carry
        v = lax.dynamic_index_in_dim(nib, j, axis=1, keepdims=False)  # [2B]
        g = select_rows(lax.dynamic_index_in_dim(g_table, j, keepdims=False), v[:B])
        q = lax.dynamic_index_in_dim(q_rows, j, axis=1, keepdims=False)
        row = jnp.concatenate([g, q])  # [2B, 2, L]
        acc, e = _madd(
            acc, fe_from_array(row[:, 0]), fe_from_array(row[:, 1]), v == 0
        )
        return acc, exc | e

    def lanes(x):  # a field constant on every chain-lane
        return tuple(jnp.full((2 * B,), v, jnp.uint32) for v in x)

    start = Point(lanes(mont_one(f)), lanes(mont_one(f)), lanes(limbs.fe_zero()))
    acc, exc = lax.fori_loop(
        0, _COMB_WINDOWS, body, (start, jnp.zeros((2 * B,), jnp.bool_))
    )
    a1 = Point(*(tuple(v[:B] for v in coord) for coord in acc))
    a2 = Point(*(tuple(v[B:] for v in coord) for coord in acc))
    res = _add_complete(a1, a2)
    inf = fe_is_zero(res.z)
    z2 = mont_sqr(f, res.z)
    # to_mont with the constant as the first factor: the product is
    # symmetric, and mont_mul takes its batch shape from the second.
    c1 = mont_mul(f, mont_mul(f, f.r2_mod, fe_from_array(scalars[:, 2])), z2)
    c2 = mont_mul(f, mont_mul(f, f.r2_mod, fe_from_array(scalars[:, 3])), z2)
    ok = fe_eq(res.x, c1) | (r2_ok & fe_eq(res.x, c2))
    return ok & ~inf & ~(exc[:B] | exc[B:]) & valid


ecdsa_verify_kernel_packed = per_mode_jit(_verify_one_packed)


def verify_batch(
    items: Sequence[Tuple[Tuple[int, int], bytes, Tuple[int, int]]],
) -> np.ndarray:
    """Convenience wrapper: prepare on host, verify on device -> [B] bool,
    through the engine's packed kernel.  The batch is padded to a power of
    two (at least 8), so that callers of many sizes share few compiles."""
    n = len(items)
    bucket = max(8, 1 << (n - 1).bit_length())
    packed = prepare_packed(items, bucket)
    return np.asarray(ecdsa_verify_kernel_packed(jnp.asarray(packed)))[:n]


# ---------------------------------------------------------------------------
# Batched signing.
#
# The reference signs serially inside the enclave (usig.c:36-76) and on the
# host for replies (crypto.go:66-77).  Here the expensive part of ECDSA
# signing — the fixed-base scalar multiplication k*G — runs as a batched
# device kernel, with the cheap big-int scalar work (RFC 6979 nonce, k^-1,
# s = k^-1(z + r*d) mod n) on the host.  Signatures are byte-identical to
# the host signer (deterministic k), which doubles as the differential
# test.  Whether a sign batch beats the serial host signer depends on the
# per-dispatch host<->device cost (to be measured on the chip).


def _bits_of(scalar_arr: jnp.ndarray) -> jnp.ndarray:
    """[16] u32 limb array -> [256] bit array, bit j = bit j of the scalar."""
    shifts = jnp.arange(limbs.LIMB_BITS, dtype=jnp.uint32)
    return ((scalar_arr[:, None] >> shifts[None, :]) & 1).reshape(256)


def _kg_one(k: jnp.ndarray) -> jnp.ndarray:
    """Scalar-shaped k*G via a dedicated G-only bit ladder: 256 iterations
    of double-then-conditionally-add-G — no Q half, so none of the verify
    ladder's G+Q table build or its Fermat inversion (~10% of the verify's
    multiplies) and a 2-way instead of 4-way addend select.  Returns X and
    Z (Jacobian, Montgomery form) stacked as one [2, 16] array — a single
    device→host transfer per batch; Y is not needed for signing.

    Kept as the differential reference for the comb kernel below (and the
    fallback if a backend dislikes the comb's table selects)."""
    bits = _bits_of(k)

    def body(i, carry):
        acc, exc = carry
        j = 255 - i
        acc = _dbl(acc)
        b = lax.dynamic_index_in_dim(bits, j, keepdims=False)
        res, e = _madd(acc, _GX_M, _GY_M, b == 0)
        return res, exc | e

    start = Point(mont_one(FIELD), mont_one(FIELD), limbs.fe_zero())
    res, exc = lax.fori_loop(0, 256, body, (start, jnp.bool_(False)))
    # exc (acc == G mid-ladder) cannot fire for scalars < n (partial sums
    # are distinct G-multiples), but fold it into Z so a hypothetical hit
    # degrades to "infinity" — sign_batch falls back to the host signer.
    z = fe_select(exc, limbs.fe_zero(), res.z)
    return jnp.stack([limbs.fe_to_array(res.x), limbs.fe_to_array(z)])


ecdsa_kg_ladder_kernel = per_mode_jit(jax.vmap(_kg_one))


# --- fixed-base comb --------------------------------------------------------
#
# k*G over G's comb table (see "Fixed-base comb tables" above): 64 mixed
# additions, no doubling.  The table is one compile-time constant shared by
# every lane, and each window's lookup is an elementwise masked sum over 16
# rows (:func:`_select_row`).

def _kg_comb_one(k: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Scalar-shaped k*G via the fixed-base comb (see the note above).
    Returns the same [2, 16] (X, Z) stack as _kg_one, narrowed to uint16
    (limbs are 16-bit; this halves the device→host transfer).

    Exceptional-case note: partial sums after window j are m*G with
    m < 16^(j+1), while window j+1 adds k_{j+1} * 16^(j+1) * G — the
    incomplete madd's p == ±q cases would need m == ±k_{j+1}*16^(j+1)
    (mod n), impossible for honest scalars < n; exc is still folded to
    Z = 0 (host-signer fallback) as defense in depth."""
    nibs = _nibbles_of(k)

    def body(j, carry):
        acc, exc = carry
        tab_j = lax.dynamic_index_in_dim(table, j, keepdims=False)  # [16,2,L]
        v = lax.dynamic_index_in_dim(nibs, j, keepdims=False)
        sel = _select_row(tab_j, v)  # [2, L]
        ax = fe_from_array(sel[0])
        ay = fe_from_array(sel[1])
        res, e = _madd(acc, ax, ay, v == 0)
        return res, exc | e

    start = Point(mont_one(FIELD), mont_one(FIELD), limbs.fe_zero())
    res, exc = lax.fori_loop(
        0, _COMB_WINDOWS, body, (start, jnp.bool_(False))
    )
    z = fe_select(exc, limbs.fe_zero(), res.z)
    out = jnp.stack([limbs.fe_to_array(res.x), limbs.fe_to_array(z)])
    return out.astype(jnp.uint16)


_kg_comb_batch = None


def kg_comb_kernel():
    """The jitted fixed-base comb kernel itself: [B, 16] uint16 nonce
    limbs -> [B, 2, 16] uint16 (X, Z).  Built on first use; the comb
    table is closed over as a jit constant — baked into the executable,
    never a per-call transfer.  (Traceable: what the AOT compile tests
    lower; :func:`ecdsa_kg_kernel` is the array-taking entry point.)"""
    global _kg_comb_batch
    if _kg_comb_batch is None:
        table = jnp.asarray(_COMB_TABLE_NP)

        def _kg_comb_widen(k16: jnp.ndarray) -> jnp.ndarray:
            # Widen the u16 upload on device; the wire carries half the
            # bytes of u32 limb rows.
            return jax.vmap(_kg_comb_one, in_axes=(0, None))(
                k16.astype(jnp.uint32), table
            )

        _kg_comb_batch = per_mode_jit(_kg_comb_widen)
    return _kg_comb_batch


def ecdsa_kg_kernel(k_arr) -> jnp.ndarray:
    """Batched k*G — fixed-base comb kernel (the sign hot path).  Takes
    [B, 16] limb rows (any integer dtype; values < 2^16), uploads them as
    uint16, and returns [B, 2, 16] uint16 (X, Z) Jacobian Montgomery."""
    return kg_comb_kernel()(jnp.asarray(np.asarray(k_arr).astype(np.uint16)))


_batch_inv = limbs.batch_inv_host

# Staging layout for the sign path: one [16] u16 nonce-limb row per lane
# (the k*G kernels upload u16 and widen on device).  The engine's sign
# queue recycles [bucket, SIGN_COLS] buffers through its _StagingPool
# exactly like the verify path's packed uploads.
SIGN_COLS = limbs.NLIMBS


def sign_prepare(
    items: Sequence[Tuple[int, bytes]],
    bucket: int,
    out: "np.ndarray | None" = None,
) -> Tuple[np.ndarray, list]:
    """Host half 1 of batched signing: derive the RFC 6979 nonce per item
    (an HMAC-SHA256 chain — inherently per-item, but cheap host hashing)
    and pack the whole batch's nonce limbs with one bulk '<u2' view
    (:func:`minbft_tpu.ops.limbs.to_limbs_batch`) into ``out`` (an
    engine-owned recycled staging buffer when given).  Pad lanes get
    k = 1 — a valid scalar whose result is discarded — as a tail write,
    never a re-derivation.  Returns ``(staging, meta)``; ``meta`` is the
    per-lane ``(d, z, k)`` list :func:`sign_finish` consumes."""
    from ..utils import hostcrypto as hc

    n = len(items)
    out = limbs.staging_out(out, bucket, SIGN_COLS, n)
    meta = []
    ks = []
    for d, digest in items:
        z = int.from_bytes(digest[:32], "big") % N
        k = hc._rfc6979_k(d, z)
        meta.append((d, z, k))
        ks.append(k)
    if n:
        out[:n] = limbs.to_limbs_batch(ks)
    out[n:] = 0
    out[n:, 0] = 1  # k = 1: a valid lane, result discarded
    return out, meta


def sign_finish(
    items: Sequence[Tuple[int, bytes]], meta: list, xz
) -> list:
    """Host half 2: turn the device's [B, 2, 16] X/Z limbs into (r, s).

    ONE Montgomery batch inversion each for the Z^2 chain (mod p) and the
    nonces (mod n) — 3 big-int multiplies per lane instead of a ~25us
    ``pow`` each (the PR-2 ``batch_inv_host`` machinery).  Exceptional
    lanes (Z == 0) and the vanishing-probability r == 0 / s == 0 RFC 6979
    retries fall back to the serial host signer per lane."""
    from ..utils import hostcrypto as hc

    b = len(meta)
    xz = np.concatenate([np.asarray(o) for o in xz]) if isinstance(
        xz, (list, tuple)
    ) else np.asarray(xz)
    xz = xz.astype("<u2")[:b]  # [B,2,16]
    # Vectorized limb→int: uint16 rows → little-endian bytes → one
    # int.from_bytes per row (a per-limb shift-sum costs ~250us/row).
    x_ints = [int.from_bytes(row.tobytes(), "little") for row in xz[:, 0]]
    z_ints = [int.from_bytes(row.tobytes(), "little") for row in xz[:, 1]]

    r_inv = pow(1 << 256, -1, P)  # undo the Montgomery factor on host
    valid = [i for i in range(b) if z_ints[i] != 0]
    zj = {i: z_ints[i] * r_inv % P for i in valid}
    zz_invs = dict(
        zip(valid, _batch_inv([zj[i] * zj[i] % P for i in valid], P))
    )
    k_invs = dict(zip(valid, _batch_inv([meta[i][2] for i in valid], N)))

    out = []
    for i, (d, z, k) in enumerate(meta):
        if i not in zz_invs:  # infinity / exceptional lane: serial fallback
            out.append(hc.ecdsa_sign_py(d, items[i][1]))
            continue
        x_aff = (x_ints[i] * r_inv % P) * zz_invs[i] % P
        r = x_aff % N
        s = k_invs[i] * (z + r * d) % N
        if r == 0 or s == 0:  # vanishing-probability RFC 6979 retry path
            out.append(hc.ecdsa_sign_py(d, items[i][1]))
            continue
        out.append((r, s))
    return out


def sign_batch(
    items: Sequence[Tuple[int, bytes]],
    bucket: int = 0,
    kg_kernel=None,
    chunk: int = 4096,
) -> list:
    """[(private scalar d, digest32)] -> [(r, s)] — RFC 6979 deterministic,
    byte-identical to :func:`minbft_tpu.utils.hostcrypto.ecdsa_sign_py`.

    ``bucket`` pads the device batch to a fixed size (pad lanes compute
    1*G and are discarded) so varying batch sizes share one compiled
    kernel — hot-path callers must pass their bucket ladder's size, like
    the verify path's engine buckets.  ``kg_kernel`` overrides the k*G
    kernel — pass :func:`minbft_tpu.parallel.mesh.sharded_ecdsa_sign_kernel`'s
    result to shard signing across a device mesh (bucket must then be a
    multiple of the mesh size).

    Composition of :func:`sign_prepare` → k*G kernel → :func:`sign_finish`
    — the engine's sign queue (:mod:`minbft_tpu.parallel.engine`) drives
    the same three stages with recycled staging buffers and a separately
    timed host/device split."""
    b = len(items)
    if b == 0 and bucket == 0:
        return []
    total = max(bucket, b)
    # Pipeline large batches through the device in fixed-size chunks: jax
    # dispatch is asynchronous, so launching every chunk before collecting
    # any overlaps chunk i's compute + device->host transfer with chunk
    # i+1's upload, where a monolithic batch serializes them (the
    # per-dispatch host<->device cost is to be measured on the chip).
    # Equal chunk shapes share one compiled kernel.
    if total > chunk:
        total = -(-total // chunk) * chunk  # round up to a chunk multiple
    k_arr, meta = sign_prepare(items, total)
    kernel = kg_kernel if kg_kernel is not None else ecdsa_kg_kernel
    step = chunk if total > chunk else total
    outs = [kernel(k_arr[c0 : c0 + step]) for c0 in range(0, total, step)]
    return sign_finish(items, meta, outs)


def is_on_curve(x: int, y: int) -> bool:
    """Host-side curve membership check for keystore loading (not hot path)."""
    if not (0 <= x < P and 0 <= y < P):
        return False
    return (y * y - (x * x * x - 3 * x + B)) % P == 0
