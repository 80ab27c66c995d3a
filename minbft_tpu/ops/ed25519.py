"""Batched Ed25519 verification as a JAX/XLA TPU kernel.

The Ed25519 authenticator path (BASELINE config[5]: n=31, batch=1024).
Same architecture as :mod:`minbft_tpu.ops.p256` — host does the cheap
irregular work, the device does the double-scalar multiplication over the
shared limb machinery (:mod:`minbft_tpu.ops.limbs`) — but the curve shape
is friendlier: twisted Edwards (a = -1) extended coordinates have
**complete** addition formulas (a is a square mod 2^255-19, d is not), so
the ladder needs *zero* exceptional-case handling: the identity is a
perfectly ordinary table entry and add(P, P) just works.

Strict cofactorless verification (OpenSSL's semantics, matching
:func:`minbft_tpu.utils.hostcrypto.ed25519_verify` — see the semantics
note there): accept iff ``compress(S*B - k*A) == R-bytes``.  Host computes
k = SHA-512(R||A||M) mod L (SHA-512 needs 64-bit ops — pointless to
emulate on device for 96-byte inputs) and decompresses A (one sqrt,
*cached per public key* — the key set is small and stable), and ships
``u1 = S``, ``u2 = k``, ``A' = -A``, and R's encoded y + sign bit.
Device computes ``P = u1*B + u2*A'`` (256 doublings + 256 *unconditional*
complete additions), normalizes it with one Fermat inversion, and accepts
iff ``(y(P), sign(x(P)))`` equals R's encoding.  R is never decompressed:
the per-signature host big-int sqrt that this replaces was the n=31
benchmark's dominant cost (~64 host pows per committed request).
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
    from_mont,
    mont_inv,
    mont_mul,
    mont_one,
    mont_sqr,
    sub_mod,
    to_limbs,
    to_mont,
)
from ..utils import hostcrypto as hc

P = hc.ED_P  # 2^255 - 19
L = hc.ED_L
D = hc.ED_D

FIELD = FieldSpec.make(P)

_BX_M = fe_const((hc.ED_BX << 256) % P)
_BY_M = fe_const((hc.ED_BY << 256) % P)
_BT_M = fe_const(((hc.ED_BX * hc.ED_BY % P) << 256) % P)
_D2_M = fe_const(((2 * D % P) << 256) % P)


class EdPoint(NamedTuple):
    """Extended twisted-Edwards point (X : Y : Z : T), Montgomery limbs."""

    x: Fe
    y: Fe
    z: Fe
    t: Fe


def _identity() -> EdPoint:
    one = mont_one(FIELD)
    zero = limbs.fe_zero()
    return EdPoint(zero, one, one, zero)


def _add(p: EdPoint, q: EdPoint) -> EdPoint:
    """Complete unified addition, a = -1 (add-2008-hwcd-3 with k = 2d).
    Handles identity and doubling inputs exactly — no special cases."""
    f = FIELD
    a = mont_mul(f, sub_mod(f, p.y, p.x), sub_mod(f, q.y, q.x))
    b = mont_mul(f, add_mod(f, p.y, p.x), add_mod(f, q.y, q.x))
    c = mont_mul(f, mont_mul(f, p.t, _D2_M), q.t)
    zz = mont_mul(f, p.z, q.z)
    d = add_mod(f, zz, zz)
    e = sub_mod(f, b, a)
    ff = sub_mod(f, d, c)
    g = add_mod(f, d, c)
    h = add_mod(f, b, a)
    return EdPoint(
        mont_mul(f, e, ff),
        mont_mul(f, g, h),
        mont_mul(f, ff, g),
        mont_mul(f, e, h),
    )


def _dbl(p: EdPoint) -> EdPoint:
    """Dedicated doubling (dbl-2008-hwcd, a = -1): 4M + 4S."""
    f = FIELD
    a = mont_sqr(f, p.x)
    b = mont_sqr(f, p.y)
    zz = mont_sqr(f, p.z)
    c = add_mod(f, zz, zz)
    # a_curve = -1: D = -A
    e = sub_mod(f, sub_mod(f, mont_sqr(f, add_mod(f, p.x, p.y)), a), b)
    g = sub_mod(f, b, a)  # D + B
    ff = sub_mod(f, g, c)
    h = sub_mod(f, limbs.fe_zero(), add_mod(f, a, b))  # D - B = -(A+B)
    return EdPoint(
        mont_mul(f, e, ff),
        mont_mul(f, g, h),
        mont_mul(f, ff, g),
        mont_mul(f, e, h),
    )


def _bits_of(scalar_arr: jnp.ndarray) -> jnp.ndarray:
    shifts = jnp.arange(limbs.LIMB_BITS, dtype=jnp.uint32)
    return ((scalar_arr[:, None] >> shifts[None, :]) & 1).reshape(256)


def _ladder(u1_arr: jnp.ndarray, u2_arr: jnp.ndarray, aq: EdPoint) -> EdPoint:
    """P = u1*B + u2*A' — interleaved ladder with *unconditional* complete
    additions: table index 0 is the identity, so every iteration is
    double-then-add with a 4-way table select and no branches at all."""
    one = mont_one(FIELD)
    bpt = EdPoint(_BX_M, _BY_M, one, _BT_M)
    ba = _add(bpt, aq)  # B + A'

    tab = [_identity(), aq, bpt, ba]  # index = 2*bit(u1) + bit(u2)
    bits1 = _bits_of(u1_arr)
    bits2 = _bits_of(u2_arr)

    def sel(d, coord):
        is1, is2 = d == 1, d == 2
        return tuple(
            jnp.where(
                is1, t1, jnp.where(is2, t2, jnp.where(d == 3, t3, t0))
            )
            for t0, t1, t2, t3 in zip(*(getattr(t, coord) for t in tab))
        )

    def body(i, acc):
        j = 255 - i
        acc = _dbl(acc)
        b1 = lax.dynamic_index_in_dim(bits1, j, keepdims=False)
        b2 = lax.dynamic_index_in_dim(bits2, j, keepdims=False)
        d = b1 * 2 + b2
        addend = EdPoint(sel(d, "x"), sel(d, "y"), sel(d, "z"), sel(d, "t"))
        return _add(acc, addend)

    return lax.fori_loop(0, 256, body, _identity())


def _verify_one(
    ax: jnp.ndarray,
    ay: jnp.ndarray,
    u1: jnp.ndarray,
    u2: jnp.ndarray,
    ry: jnp.ndarray,
    rsign: jnp.ndarray,
    valid: jnp.ndarray,
) -> jnp.ndarray:
    """Scalar-shaped Ed25519 verify core; limb-array args [16] u32.

    Accepts iff compress(u1*B + u2*A') matches (ry, rsign) — the affine
    normalization (one Fermat inversion) runs on device; Z is never 0
    under complete formulas on curve points."""
    f = FIELD
    ax_m = to_mont(f, fe_from_array(ax))
    ay_m = to_mont(f, fe_from_array(ay))
    at_m = mont_mul(f, ax_m, ay_m)
    aq = EdPoint(ax_m, ay_m, mont_one(f), at_m)
    res = _ladder(u1, u2, aq)
    zi = mont_inv(f, res.z)
    x_aff = from_mont(f, mont_mul(f, res.x, zi))
    y_aff = from_mont(f, mont_mul(f, res.y, zi))
    ok_y = fe_eq(y_aff, fe_from_array(ry))
    ok_sign = (x_aff[0] & np.uint32(1)) == rsign
    return ok_y & ok_sign & valid


from .lowering import per_mode_jit

ed25519_verify_kernel = per_mode_jit(jax.vmap(_verify_one))


# ---------------------------------------------------------------------------
# Host-side batch preparation.


import functools


@functools.lru_cache(maxsize=4096)
def _neg_pub_limbs(pub: bytes):
    """pub32 -> (limbs of -A.x, limbs of A.y), or None if not a curve
    point.  Decompression (a big-int sqrt) and limb packing both cached:
    the cluster's key set is small and every signature reuses it."""
    a_pt = hc.ed_decompress(pub)
    if a_pt is None:
        return None
    x, y = a_pt[0], a_pt[1]  # decompress returns Z = 1
    return to_limbs((P - x) % P if x else 0), to_limbs(y)


_ZERO64 = b"\x00" * 64
_L_WORDS = limbs.words_of(L)
_P_WORDS = limbs.words_of(P)


def prepare_batch_scalar(
    items: Sequence[Tuple[bytes, bytes, bytes]], bucket: int
) -> Tuple[np.ndarray, ...]:
    """Per-item reference prep — the differential ORACLE for the
    vectorized :func:`prepare_batch`, kept verbatim."""
    import hashlib

    b = bucket
    ax = np.zeros((b, limbs.NLIMBS), np.uint32)
    ay = np.zeros((b, limbs.NLIMBS), np.uint32)
    u1 = np.zeros((b, limbs.NLIMBS), np.uint32)
    u2 = np.zeros((b, limbs.NLIMBS), np.uint32)
    ry = np.zeros((b, limbs.NLIMBS), np.uint32)
    rsign = np.zeros((b,), np.uint32)
    valid = np.zeros((b,), np.bool_)
    for i, (pub, msg, sig) in enumerate(items):
        if len(sig) != 64:
            continue
        a_limbs = _neg_pub_limbs(pub)
        if a_limbs is None:
            continue
        s = int.from_bytes(sig[32:], "little")
        if s >= L:
            continue
        y_enc = int.from_bytes(sig[:32], "little")
        y_r = y_enc & ((1 << 255) - 1)
        if y_r >= P:
            continue  # non-canonical R encoding (strict semantics)
        k = (
            int.from_bytes(
                hashlib.sha512(sig[:32] + pub + msg).digest(), "little"
            )
            % L
        )
        ax[i], ay[i] = a_limbs  # A' = -A
        u1[i] = to_limbs(s)
        u2[i] = to_limbs(k)
        ry[i] = to_limbs(y_r)
        rsign[i] = y_enc >> 255
        valid[i] = True
    return ax, ay, u1, u2, ry, rsign, valid


def prepare_batch(
    items: Sequence[Tuple[bytes, bytes, bytes]], bucket: int
) -> Tuple[np.ndarray, ...]:
    """[(pub32, msg, sig64)] -> device-ready limb arrays, padded to
    ``bucket`` lanes.  Malformed/non-canonical inputs get valid=False.

    Vectorized (round-6, same division of labor as
    :func:`minbft_tpu.ops.p256.prepare_batch`): the only remaining
    per-item host work is one SHA-512 (64-bit ops — pointless to batch on
    host or emulate on device) and the per-public-key decompression
    cache.  Everything else is whole-batch numpy: the signature's s and
    R-encoding halves are '<u2' views of the concatenated sig bytes (the
    16-bit limb layout IS the wire layout), the s < L / y_r < p
    canonicality checks are vectorized word compares, and the only
    inversion-bearing prep (A's decompression sqrt) stays cached per key
    — the sign path's compression already batch-inverts
    (:func:`minbft_tpu.ops.limbs.batch_inv_host`).  Bit-identical to
    :func:`prepare_batch_scalar`.
    """
    import hashlib

    b = bucket
    n = len(items)
    nl = limbs.NLIMBS
    ax = np.zeros((b, nl), np.uint32)
    ay = np.zeros((b, nl), np.uint32)
    u1 = np.zeros((b, nl), np.uint32)
    u2 = np.zeros((b, nl), np.uint32)
    ry = np.zeros((b, nl), np.uint32)
    rsign = np.zeros((b,), np.uint32)
    valid = np.zeros((b,), np.bool_)
    if n == 0:
        return ax, ay, u1, u2, ry, rsign, valid

    # Pass 1 (per item): structural sig check + cached decompression.
    sigbuf = bytearray()
    a_rows: list = []
    ok = np.zeros((n,), np.bool_)
    for i, (pub, _msg, sig) in enumerate(items):
        a_limbs = _neg_pub_limbs(pub) if len(sig) == 64 else None
        if a_limbs is None:
            sigbuf += _ZERO64
            a_rows.append(None)
            continue
        sigbuf += sig
        a_rows.append(a_limbs)
        ok[i] = True

    raw = bytes(sigbuf)
    srows = np.frombuffer(raw, dtype="<u2").reshape(n, 2, nl)
    swords = np.frombuffer(raw, dtype="<u8").reshape(n, 2, 4)
    ry16 = srows[:, 0].copy()
    rsign_n = (ry16[:, nl - 1] >> 15).astype(np.uint32)
    ry16[:, nl - 1] &= 0x7FFF  # y_r = y_enc & (2^255 - 1)

    # Vectorized canonicality: s < L, y_r < p (strict semantics).
    ok &= limbs.words_lt(swords[:, 1], _L_WORDS)
    ok &= limbs.words_lt(limbs.limb_words(ry16), _P_WORDS)

    # Pass 2 (valid lanes only): one SHA-512 per lane for the challenge k.
    vidx = np.flatnonzero(ok)
    idx = vidx.tolist()
    if idx:
        sha = hashlib.sha512
        k_ints = []
        for i in idx:
            pub, msg, sig = items[i]
            k_ints.append(
                int.from_bytes(sha(sig[:32] + pub + msg).digest(), "little")
                % L
            )
        ax[vidx] = np.stack([a_rows[i][0] for i in idx])
        ay[vidx] = np.stack([a_rows[i][1] for i in idx])
        u1[vidx] = srows[vidx, 1]
        u2[vidx] = limbs.to_limbs_batch(k_ints)
        ry[vidx] = ry16[vidx]
        rsign[vidx] = rsign_n[vidx]
        valid[vidx] = True
    return ax, ay, u1, u2, ry, rsign, valid


# Packed I/O (see ops/p256.py PACKED_COLS note): one u16 upload per
# dispatch instead of seven array RPCs — limb values are 16-bit by
# construction, rsign/valid are 0/1.

PACKED_COLS = 5 * limbs.NLIMBS + 2  # ax ay u1 u2 ry | rsign valid


def pack_arrays(arrays) -> np.ndarray:
    ax, ay, u1, u2, ry, rsign, valid = arrays
    return np.concatenate(
        [
            ax, ay, u1, u2, ry,
            rsign[:, None].astype(np.uint32),
            valid[:, None].astype(np.uint32),
        ],
        axis=1,
    ).astype(np.uint16)


def prepare_packed(
    items: Sequence[Tuple[bytes, bytes, bytes]],
    bucket: int,
    out: "np.ndarray | None" = None,
) -> np.ndarray:
    """prepare_batch + pack_arrays fused into one [bucket, PACKED_COLS]
    u16 staging write (see :func:`minbft_tpu.ops.p256.prepare_packed`);
    ``out`` is an engine-owned recycled staging buffer."""
    n = len(items)
    out = limbs.staging_out(out, bucket, PACKED_COLS, n)
    ax, ay, u1, u2, ry, rsign, valid = prepare_batch(items, bucket)
    L_ = limbs.NLIMBS
    out[:, 0:L_] = ax
    out[:, L_ : 2 * L_] = ay
    out[:, 2 * L_ : 3 * L_] = u1
    out[:, 3 * L_ : 4 * L_] = u2
    out[:, 4 * L_ : 5 * L_] = ry
    out[:, 5 * L_] = rsign
    out[:, 5 * L_ + 1] = valid
    return out


# Named apart from ops/p256.py's ``_verify_one_packed``: the jit's name is
# what a profiler trace shows, and a deployment may run both kernels.
def _ed25519_verify_one_packed(row: jnp.ndarray) -> jnp.ndarray:
    r32 = row.astype(jnp.uint32)
    L_ = limbs.NLIMBS
    return _verify_one(
        r32[0:L_],
        r32[L_ : 2 * L_],
        r32[2 * L_ : 3 * L_],
        r32[3 * L_ : 4 * L_],
        r32[4 * L_ : 5 * L_],
        r32[5 * L_],
        r32[5 * L_ + 1] != 0,
    )


ed25519_verify_kernel_packed = per_mode_jit(jax.vmap(_ed25519_verify_one_packed))


def verify_batch_padded(
    items: Sequence[Tuple[bytes, bytes, bytes]], bucket: int
) -> np.ndarray:
    """Engine dispatch hook: prepare on host, verify on device -> [bucket]
    bool (lanes past len(items) are padding).  Packed single-upload path."""
    packed = prepare_packed(items, bucket)
    return np.asarray(ed25519_verify_kernel_packed(jnp.asarray(packed)))


def verify_batch(items: Sequence[Tuple[bytes, bytes, bytes]]) -> np.ndarray:
    return verify_batch_padded(items, len(items))[: len(items)]


# ---------------------------------------------------------------------------
# Batched signing: the expensive half of RFC 8032 signing is the fixed-base
# scalar multiplication r*B — the same comb that carried ECDSA signing
# (ops/p256.py, see the note there), and simpler here because the Edwards
# addition is COMPLETE: the v = 0 table rows are literally the identity
# point and flow through _add with no flags or exceptional cases at all.
# Host does the SHA-512 scalar derivations and the final compression
# (one Montgomery batch inversion for the whole batch).

_COMB_WINDOWS = 64
_COMB_TABLE_NP: np.ndarray | None = None


def _comb_table_np() -> np.ndarray:
    """[64, 16, 3, NLIMBS] u32: (x, y, t=xy) affine Montgomery rows of
    v * 16^j * B; v = 0 rows are the identity (0, 1, 0)."""
    global _COMB_TABLE_NP
    if _COMB_TABLE_NP is not None:
        return _COMB_TABLE_NP
    tab = np.zeros((_COMB_WINDOWS, 16, 3, limbs.NLIMBS), np.uint32)
    one_m = to_limbs((1 << 256) % P)
    for j in range(_COMB_WINDOWS):
        tab[j, 0, 1] = one_m  # identity: (0 : 1 : 1 : 0)
    base = hc.ED_BASE  # extended affine-ish host tuple (x, y, z=1, t)
    for j in range(_COMB_WINDOWS):
        acc = None
        for v in range(1, 16):
            acc = base if acc is None else hc.ed_add(acc, base)
            x, y, z, _t = acc
            zi = pow(z, -1, P)
            xa, ya = x * zi % P, y * zi % P
            tab[j, v, 0] = to_limbs((xa << 256) % P)
            tab[j, v, 1] = to_limbs((ya << 256) % P)
            tab[j, v, 2] = to_limbs((xa * ya % P << 256) % P)
        base = hc.ed_scalar_mult(16, base)
    _COMB_TABLE_NP = tab
    return tab


def _rb_comb_one(r: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Scalar-shaped r*B via the fixed-base comb -> [3, NLIMBS] u16
    (X, Y, Z extended coords, Montgomery domain; narrow transfer)."""
    one = mont_one(FIELD)
    shifts = (4 * jnp.arange(4, dtype=jnp.uint32))[None, :]
    nibs = ((r[:, None] >> shifts) & 0xF).reshape(_COMB_WINDOWS)

    def body(j, acc):
        tab_j = lax.dynamic_index_in_dim(table, j, keepdims=False)  # [16,3,L]
        v = lax.dynamic_index_in_dim(nibs, j, keepdims=False)
        mask = (jnp.arange(16, dtype=jnp.uint32) == v)[:, None, None]
        sel = jnp.sum(jnp.where(mask, tab_j, 0), axis=0)  # [3, L]
        q = EdPoint(
            fe_from_array(sel[0]), fe_from_array(sel[1]), one,
            fe_from_array(sel[2]),
        )
        return _add(acc, q)

    res = lax.fori_loop(0, _COMB_WINDOWS, body, _identity())
    out = jnp.stack(
        [
            limbs.fe_to_array(res.x),
            limbs.fe_to_array(res.y),
            limbs.fe_to_array(res.z),
        ]
    )
    return out.astype(jnp.uint16)


_rb_comb_batch = None


def rb_comb_kernel():
    """The jitted fixed-base comb kernel itself: [B, 16] uint16 nonce
    limbs -> [B, 3, 16] uint16.  Built on first use, table closed over
    as a jit constant (never a per-call upload).  Traceable — see
    :func:`minbft_tpu.ops.p256.kg_comb_kernel`."""
    global _rb_comb_batch
    if _rb_comb_batch is None:
        table = jnp.asarray(_comb_table_np())

        def _rb_comb_widen(r16):
            return jax.vmap(_rb_comb_one, in_axes=(0, None))(
                r16.astype(jnp.uint32), table
            )

        _rb_comb_batch = per_mode_jit(_rb_comb_widen)
    return _rb_comb_batch


def ed25519_rb_kernel(r_arr) -> jnp.ndarray:
    """Batched r*B — [B, 16] limb rows in (uploaded u16), [B, 3, 16] u16
    out."""
    return rb_comb_kernel()(jnp.asarray(np.asarray(r_arr).astype(np.uint16)))


_batch_inv = limbs.batch_inv_host

# Staging layout for the sign path (see ops/p256.py SIGN_COLS): one [16]
# u16 nonce-limb row per lane, recyclable through the engine's pool.
SIGN_COLS = limbs.NLIMBS


def sign_prepare(
    items: Sequence[Tuple[bytes, bytes]],
    bucket: int,
    out: "np.ndarray | None" = None,
) -> Tuple[np.ndarray, tuple]:
    """Host half 1 of batched Ed25519 signing: the RFC 8032 SHA-512
    scalar derivations, with the whole batch's nonce limbs packed into
    ``out`` (engine staging buffer when given) via one bulk conversion.
    Pad lanes get r = 1 (valid, discarded).  Returns ``(staging, meta)``
    for :func:`sign_finish`."""
    import hashlib

    n = len(items)
    out = limbs.staging_out(out, bucket, SIGN_COLS, n)
    # Per-seed derivation cache: the production shape is ONE signer, many
    # messages — the SHA-512 seed expansion, clamp, and public key are
    # computed once per distinct seed, not per item.
    per_seed: dict = {}
    rs = []
    lanes = []
    for seed, msg in items:
        entry = per_seed.get(seed)
        if entry is None:
            h = hashlib.sha512(seed).digest()
            a = int.from_bytes(h[:32], "little")
            a = (a & ((1 << 254) - 8)) | (1 << 254)
            entry = (a, h[32:], hc.ed25519_keygen(seed)[1])
            per_seed[seed] = entry
        a, prefix, pub = entry
        r = (
            int.from_bytes(hashlib.sha512(prefix + msg).digest(), "little")
            % L
        )
        rs.append(r)
        lanes.append((a, pub, msg))
    if n:
        out[:n] = limbs.to_limbs_batch(rs)
    out[n:] = 0
    out[n:, 0] = 1  # r = 1: a valid lane, result discarded
    return out, (rs, lanes)


def sign_finish(meta: tuple, xyz) -> list:
    """Host half 2: batch-invert the device Zs (ONE Montgomery sweep),
    compress R, and finish s = r + k*a per lane (RFC 8032)."""
    import hashlib

    rs, lanes = meta
    b = len(lanes)
    xyz = np.concatenate([np.asarray(o) for o in xyz]) if isinstance(
        xyz, (list, tuple)
    ) else np.asarray(xyz)
    xyz = xyz[:b]  # [B,3,16] u16

    # No Montgomery undo needed: the R factor cancels in the X/Z and Y/Z
    # ratios ((X*R) * (Z*R)^-1 == X/Z), so the raw device limbs feed the
    # batch inversion directly.
    ints = [
        [int.from_bytes(row.astype("<u2").tobytes(), "little") for row in lane]
        for lane in xyz
    ]
    z_invs = _batch_inv([lane[2] for lane in ints], P)
    out = []
    for i, (a, pub, msg) in enumerate(lanes):
        x, y, _z = ints[i]
        zi = z_invs[i]
        xa, ya = x * zi % P, y * zi % P
        rp = (ya | ((xa & 1) << 255)).to_bytes(32, "little")
        k = (
            int.from_bytes(hashlib.sha512(rp + pub + msg).digest(), "little")
            % L
        )
        s = (rs[i] + k * a) % L
        out.append(rp + s.to_bytes(32, "little"))
    return out


def sign_batch(
    items: Sequence[Tuple[bytes, bytes]],
    bucket: int = 0,
    chunk: int = 4096,
    rb_kernel=None,
) -> list:
    """[(seed32, msg)] -> [signature64] — RFC 8032 deterministic,
    byte-identical to :func:`minbft_tpu.utils.hostcrypto.ed25519_sign`.
    Device computes r*B (the comb); host derives the scalars (SHA-512),
    batch-inverts the Zs for compression, and finishes s = r + k*a —
    :func:`sign_prepare` → r*B kernel → :func:`sign_finish`, the same
    three stages the engine's sign queue drives with recycled staging.

    Shape discipline matches :func:`minbft_tpu.ops.p256.sign_batch`:
    ``bucket`` pads to a fixed size, and anything larger is padded up to a
    multiple of ``chunk`` (pad lanes compute 1*B and are discarded) so
    varying batch sizes share compiled kernels — a fresh shape costs a
    ~15s compile — while chunked launches pipeline the transfers."""
    b = len(items)
    if b == 0 and bucket == 0:
        return []
    total = max(bucket, b)
    if total > chunk:
        total = -(-total // chunk) * chunk
    r_arr, meta = sign_prepare(items, total)
    kernel = rb_kernel if rb_kernel is not None else ed25519_rb_kernel
    step = chunk if total > chunk else total
    outs = [kernel(r_arr[c0 : c0 + step]) for c0 in range(0, total, step)]
    return sign_finish(meta, outs)
