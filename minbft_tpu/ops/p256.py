"""Batched ECDSA-P256 verification as a JAX/XLA TPU kernel.

This is the north-star hot path: the reference verifies every PREPARE/COMMIT
UI certificate and client signature serially on CPU (Go crypto/ecdsa at
sample/authentication/crypto.go:79-89; enclave-side create at
usig/sgx/enclave/usig.c:36-76, verification in pure Go at
usig/sgx/sgx-usig.go:81-97).  Here a whole batch of verifications runs as one
data-parallel XLA program: ``jax.vmap`` over a scalar-shaped verifier whose
field arithmetic is the fused limb machinery of :mod:`minbft_tpu.ops.limbs`.

Division of labor (TPU-first):

- **Host** hashes variable-length bytes to the fixed 32-byte digest ``z``
  (:func:`minbft_tpu.messages.authen_digest`) and computes the two scalars
  ``u1 = z*s^-1 mod n`` and ``u2 = r*s^-1 mod n`` with native big-int ops —
  cheap, and it keeps mod-n arithmetic off the device entirely.  The
  per-batch cost is bounded by Montgomery batch inversion (ONE ``pow``
  per batch — 3 big-int multiplies per lane) and whole-batch numpy limb
  packing/range checks; see the "Host-side batch preparation" section.
- **Device** does everything expensive: the 256-bit double-scalar
  multiplication ``u1*G + u2*Q`` (interleaved Shamir ladder, Jacobian
  coordinates, a = -3 doubling), one Fermat inversion to build the G+Q
  table entry, and the affine-free final check ``X == r * Z^2`` — all
  constant-shape, batched, jit-compiled once per batch bucket.

Adversarial-input policy: the mixed-addition formula is incomplete (it
cannot add a point to itself).  Instead of paying a full doubling inside
every ladder add, the kernel *detects* the exceptional case and marks the
lane rejected (``exc`` flag).  Honest signatures hit it with probability
~2^-250; crafted signatures that steer the ladder into a collision are
simply rejected, which is always sound — the kernel only ever errs toward
rejection.  Identity operands (ladder start, Q == -G table entry) are
handled exactly with constant-shape selects.
"""

from __future__ import annotations

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
    mont_inv,
    mont_mul,
    mont_one,
    mont_sqr,
    sub_mod,
    to_limbs,
    to_mont,
)

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


def _madd_complete_table(p: Point, qx: Fe, qy: Fe, q_inf: jnp.ndarray) -> Point:
    """madd with the doubling case handled exactly (one extra _dbl) — used
    once per verify to build the G+Q table entry, where Q == G must yield 2G
    (a legitimate, if weird, public key)."""
    res, exc = _madd(p, qx, qy, q_inf)
    d = _dbl(p)
    return Point(
        fe_select(exc, d.x, res.x),
        fe_select(exc, d.y, res.y),
        fe_select(exc, d.z, res.z),
    )


def _bits_of(scalar_arr: jnp.ndarray) -> jnp.ndarray:
    """[16] u32 limb array -> [256] bit array, bit j = bit j of the scalar."""
    shifts = jnp.arange(limbs.LIMB_BITS, dtype=jnp.uint32)
    return ((scalar_arr[:, None] >> shifts[None, :]) & 1).reshape(256)


def _shamir(
    u1_arr: jnp.ndarray, u2_arr: jnp.ndarray, qx_m: Fe, qy_m: Fe
) -> Tuple[Point, jnp.ndarray]:
    """Interleaved double-scalar multiplication u1*G + u2*Q.

    256 iterations of double-then-select-add against the affine table
    {-, Q, G, G+Q} (indexed by 2*bit(u1) + bit(u2)); the G+Q entry is built
    on device with one Fermat inversion.  One ``fori_loop``: the compiled
    program is a handful of loop nodes regardless of batch size.

    Measured dead end (round 3, v5e, batch 4096/16384): signed-window
    ladders (w=4 and w=5, host-precomputed G tables, device-built Jacobian
    Q tables, ~30% fewer field multiplies than this ladder) are *slower*
    here — 77-86k verifies/s vs 110-113k at 4096 — and compile 2-4x
    longer.  Mosaic schedules this tiny loop body (~19 mults) near peak
    VPU throughput, while the windowed bodies (~60 mults + 9-17-entry
    per-lane tables live across the loop) lose more to scheduling and
    vector-memory pressure than the multiply count saves; per-lane
    dynamic gathers for table lookups are 6x worse still.  The batch
    size, not the ladder, is the remaining lever: larger batches
    amortize the per-dispatch host<->device cost (to be measured on the
    chip).

    Returns (result, exc) — exc set if any ladder add hit the incomplete
    case (lane must be rejected; see module docstring).
    """
    f = FIELD
    one = mont_one(f)
    gx: Fe = _GX_M
    gy: Fe = _GY_M

    # Table entry G+Q (affine).  Q == ±G handled exactly.
    gq = _madd_complete_table(Point(gx, gy, one), qx_m, qy_m, jnp.bool_(False))
    gq_inf = fe_is_zero(gq.z)
    zsafe = fe_select(gq_inf, one, gq.z)
    zi = mont_inv(f, zsafe)
    zi2 = mont_sqr(f, zi)
    gqx = mont_mul(f, gq.x, zi2)
    gqy = mont_mul(f, gq.y, mont_mul(f, zi, zi2))

    bits1 = _bits_of(u1_arr)
    bits2 = _bits_of(u2_arr)

    def body(i, carry):
        acc, exc = carry
        j = 255 - i
        acc = _dbl(acc)
        b1 = lax.dynamic_index_in_dim(bits1, j, keepdims=False)
        b2 = lax.dynamic_index_in_dim(bits2, j, keepdims=False)
        d = b1 * 2 + b2
        # Select the table entry with elementwise masks (no gathers).
        is1, is2, is3 = d == 1, d == 2, d == 3
        ax = fe_select(is1, qx_m, fe_select(is2, gx, gqx))
        ay = fe_select(is1, qy_m, fe_select(is2, gy, gqy))
        ainf = jnp.where(d == 0, jnp.bool_(True), is3 & gq_inf)
        res, e = _madd(acc, ax, ay, ainf)
        return res, exc | e

    start = Point(one, one, limbs.fe_zero())  # identity
    return lax.fori_loop(0, 256, body, (start, jnp.bool_(False)))


def _verify_one(
    qx: jnp.ndarray,
    qy: jnp.ndarray,
    u1: jnp.ndarray,
    u2: jnp.ndarray,
    r: jnp.ndarray,
    r2: jnp.ndarray,
    r2_ok: jnp.ndarray,
    valid: jnp.ndarray,
) -> jnp.ndarray:
    """Scalar-shaped ECDSA verify core; limb-array args [16] u32.

    Checks x(R) ≡ r (mod n) without an affine conversion: with R = (X:Y:Z)
    Jacobian, x(R) = X/Z^2, so x(R) == c  <=>  X == c*Z^2 (all Montgomery).
    Host supplies both candidates c ∈ {r, r+n} (the second only when
    r+n < p, flagged by ``r2_ok``).

    ``valid`` carries host-side range checks (r, s in [1, n-1]); the kernel
    AND-folds it so invalid inputs burn the same cycles as valid ones
    (constant shape) but always return False.
    """
    f = FIELD
    qx_m = to_mont(f, fe_from_array(qx))
    qy_m = to_mont(f, fe_from_array(qy))
    res, exc = _shamir(u1, u2, qx_m, qy_m)
    inf = fe_is_zero(res.z)
    z2 = mont_sqr(f, res.z)
    c1 = mont_mul(f, to_mont(f, fe_from_array(r)), z2)
    c2 = mont_mul(f, to_mont(f, fe_from_array(r2)), z2)
    ok = fe_eq(res.x, c1) | (r2_ok & fe_eq(res.x, c2))
    return ok & ~inf & ~exc & valid


from .lowering import per_mode_jit

_verify_batch = per_mode_jit(jax.vmap(_verify_one))


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
# the differential oracle (tests assert packed-array identity) and as a
# runtime escape hatch (MINBFT_SCALAR_PREP=1).

_ZERO128 = b"\x00" * 128  # one all-zero packed record (r | s | x | y)
_N_WORDS = limbs.words_of(N)
_P_WORDS = limbs.words_of(P)
_PN_WORDS = limbs.words_of(P - N)  # r + n < p  <=>  r < p - n


def prepare_batch_scalar(
    items: Sequence[Tuple[Tuple[int, int], bytes, Tuple[int, int]]],
) -> Tuple[np.ndarray, ...]:
    """Per-item reference prep: one ``pow(s, -1, N)`` and six ``to_limbs``
    per lane.  The differential ORACLE for the vectorized
    :func:`prepare_batch` — kept verbatim, selectable via
    MINBFT_SCALAR_PREP=1."""
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
    if limbs.SCALAR_PREP:
        return prepare_batch_scalar(items)
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


def verify_batch(
    items: Sequence[Tuple[Tuple[int, int], bytes, Tuple[int, int]]],
) -> np.ndarray:
    """Convenience wrapper: prepare on host, verify on device -> [B] bool."""
    arrays = prepare_batch(items)
    return np.asarray(_verify_batch(*[jnp.asarray(a) for a in arrays]))


ecdsa_verify_kernel = _verify_batch  # the raw jitted batch entry point


# Packed I/O: each host->device array is its own transfer, and the
# 8-argument form pays 8 of them per dispatch (per-dispatch host<->device
# cost, to be measured on the chip).  One u16 row per lane — limb values
# are 16-bit by construction, flags are 0/1 — makes the upload a single
# transfer at half the bytes.

PACKED_COLS = 6 * limbs.NLIMBS + 2  # qx qy u1 u2 r r2 | r2_ok valid


def pack_arrays(arrays) -> np.ndarray:
    """prepare_batch output -> [B, PACKED_COLS] u16 (one upload)."""
    qx, qy, u1, u2, rr, r2, r2_ok, valid = arrays
    return np.concatenate(
        [
            qx, qy, u1, u2, rr, r2,
            r2_ok[:, None].astype(np.uint32),
            valid[:, None].astype(np.uint32),
        ],
        axis=1,
    ).astype(np.uint16)


def prepare_packed(
    items: Sequence[Tuple[Tuple[int, int], bytes, Tuple[int, int]]],
    bucket: int,
    out: "np.ndarray | None" = None,
) -> np.ndarray:
    """prepare_batch + pack_arrays fused into one [bucket, PACKED_COLS]
    u16 staging write.  ``out`` (engine-owned staging buffer, recycled
    across dispatches) is written in place when given; padding the batch
    to ``bucket`` is a tail slice-zero instead of materializing
    ``list(items) + [PAD] * k`` and prepping the pad lanes."""
    n = len(items)
    out = limbs.staging_out(out, bucket, PACKED_COLS, n)
    qx, qy, u1, u2, rr, r2, r2_ok, valid = prepare_batch(items)
    L = limbs.NLIMBS
    out[:n, 0:L] = qx
    out[:n, L : 2 * L] = qy
    out[:n, 2 * L : 3 * L] = u1
    out[:n, 3 * L : 4 * L] = u2
    out[:n, 4 * L : 5 * L] = rr
    out[:n, 5 * L : 6 * L] = r2
    out[:n, 6 * L] = r2_ok
    out[:n, 6 * L + 1] = valid
    out[n:] = 0
    return out


def _verify_one_packed(row: jnp.ndarray) -> jnp.ndarray:
    r32 = row.astype(jnp.uint32)
    L = limbs.NLIMBS
    return _verify_one(
        r32[0:L],
        r32[L : 2 * L],
        r32[2 * L : 3 * L],
        r32[3 * L : 4 * L],
        r32[4 * L : 5 * L],
        r32[5 * L : 6 * L],
        r32[6 * L] != 0,
        r32[6 * L + 1] != 0,
    )


ecdsa_verify_kernel_packed = per_mode_jit(jax.vmap(_verify_one_packed))


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
# k*G with G fixed admits a precomputed-table comb that the general ladder
# cannot use: write k = sum_j k_j * 16^j over 64 nibble windows and
# precompute T[j][v] = v * 16^j * G (affine, Montgomery domain) ON THE HOST
# — then k*G = sum_j T[j][k_j] is just 64 mixed additions with NO doublings
# (~7x fewer field multiplies than the 256 double+add ladder).  The
# windowed approach measured as a dead end for the VERIFY ladder (see
# _shamir's note) fails on per-lane runtime tables; here the table is one
# global compile-time constant shared by every lane, and each window's
# lookup is an elementwise masked sum over 16 rows — no gathers, nothing
# per-lane resident across the loop.

_COMB_WINDOWS = 64
_COMB_TABLE_NP: np.ndarray | None = None


def _comb_table_np() -> np.ndarray:
    """[64, 16, 2, NLIMBS] u32: T[j][v] = affine(v * 16^j * G), Montgomery
    domain; the v=0 rows are zeros (skipped via the q_inf flag).  Built
    once with host big-int affine arithmetic (~1k cheap ops)."""
    global _COMB_TABLE_NP
    if _COMB_TABLE_NP is not None:
        return _COMB_TABLE_NP

    def aff_add(p1, p2):
        if p1 is None:
            return p2
        (x1, y1), (x2, y2) = p1, p2
        if x1 == x2:
            if (y1 + y2) % P == 0:
                return None
            lam = (3 * x1 * x1 - 3) * pow(2 * y1, -1, P) % P
        else:
            lam = (y2 - y1) * pow(x2 - x1, -1, P) % P
        x3 = (lam * lam - x1 - x2) % P
        return x3, (lam * (x1 - x3) - y1) % P

    tab = np.zeros((_COMB_WINDOWS, 16, 2, limbs.NLIMBS), np.uint32)
    base = (GX, GY)  # 16^j * G for the current window
    for j in range(_COMB_WINDOWS):
        acc = None
        for v in range(1, 16):
            acc = aff_add(acc, base)
            x, y = acc
            tab[j, v, 0] = to_limbs((x << 256) % P)
            tab[j, v, 1] = to_limbs((y << 256) % P)
        for _ in range(4):  # base <- 16 * base
            base = aff_add(base, base)
    _COMB_TABLE_NP = tab
    return tab


def _kg_comb_one(k: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Scalar-shaped k*G via the fixed-base comb (see the note above).
    Returns the same [2, 16] (X, Z) stack as _kg_one, narrowed to uint16
    (limbs are 16-bit; this halves the device→host transfer).

    Exceptional-case note: partial sums after window j are m*G with
    m < 16^(j+1), while window j+1 adds k_{j+1} * 16^(j+1) * G — the
    incomplete madd's p == ±q cases would need m == ±k_{j+1}*16^(j+1)
    (mod n), impossible for honest scalars < n; exc is still folded to
    Z = 0 (host-signer fallback) as defense in depth."""
    # limb i (16 bits) holds nibble windows 4i..4i+3
    shifts = (4 * jnp.arange(4, dtype=jnp.uint32))[None, :]
    nibs = ((k[:, None] >> shifts) & 0xF).reshape(_COMB_WINDOWS)

    def body(j, carry):
        acc, exc = carry
        tab_j = lax.dynamic_index_in_dim(table, j, keepdims=False)  # [16,2,L]
        v = lax.dynamic_index_in_dim(nibs, j, keepdims=False)
        mask = (jnp.arange(16, dtype=jnp.uint32) == v)[:, None, None]
        sel = jnp.sum(jnp.where(mask, tab_j, 0), axis=0)  # [2, L]
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
        table = jnp.asarray(_comb_table_np())

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
