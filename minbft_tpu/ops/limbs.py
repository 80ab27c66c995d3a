"""256-bit modular arithmetic as fixed-width limb tuples for TPU.

XLA on TPU has no big-int and no native 64-bit integer multiply, so field
elements are represented as **16 little-endian limbs of 16 bits each**, one
uint32 *scalar* per limb (a tuple of 16 tracers).  Under ``jax.vmap`` each
limb becomes a dense [B] lane vector — every operation below is pure
elementwise dataflow with zero gathers/slices, which is exactly what XLA's
fusion wants: a whole Montgomery multiply compiles to straight-line fused
vector code.

Design points, measured on a real TPU chip (v5e) against alternatives:

- **Lazy-carry CIOS Montgomery multiply** (:func:`mont_mul`): the classic
  word-by-word CIOS loop, but with *no* per-iteration carry propagation.
  Column accumulators receive at most four 16-bit addends per iteration, so
  over 16 iterations they stay < 2^22 — far from uint32 overflow — and a
  single carry pass at the end suffices.  The low word needed for the
  reduction quotient is exact at every step because column 0 never has
  un-received carries.  This cut the sequential dependency depth ~10x vs
  an eager-carry loop version.
- **Statically indexed**: no ``dynamic_slice``; the product schedule is a
  Python loop at trace time.  The default "block" lowering runs the outer
  CIOS loop as a 4-step ``lax.scan`` of 4 unrolled iterations each —
  measured faster than the fully unrolled straight-line form on v5e
  (122.8k vs 102.8k verifies/s at batch 4096) at ~10x less compile time;
  the fully-unrolled and per-iteration-scan forms remain as selectable
  lowerings (see :mod:`minbft_tpu.ops.lowering`).
- Long-running control flow (the 256-bit scalar ladder, Fermat powering)
  stays in ``lax.fori_loop`` *outside* this module so the HLO stays small.

This replaces the serial host big-int arithmetic of the reference (Go
crypto/ecdsa under sample/authentication/crypto.go:79-89 and the SGX
enclave's ECDSA in usig/sgx/enclave/usig.c:36-76) with a batchable
data-parallel substrate.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

import jax.numpy as jnp
from jax import lax

NLIMBS = 16
LIMB_BITS = 16
MASK = np.uint32(0xFFFF)
BITS = NLIMBS * LIMB_BITS  # 256

# A field element: 16 uint32 "scalars" (|| [B] vectors under vmap).
Fe = Tuple[jnp.ndarray, ...]


# ---------------------------------------------------------------------------
# Host-side conversions (Python int <-> limbs).


def to_limbs(x: int) -> np.ndarray:
    """Python int (< 2^256) -> [16] uint32 little-endian 16-bit limbs."""
    if not 0 <= x < (1 << BITS):
        raise ValueError("value out of 256-bit range")
    return np.array(
        [(x >> (LIMB_BITS * i)) & 0xFFFF for i in range(NLIMBS)], dtype=np.uint32
    )


def from_limbs(limbs) -> int:
    """[16] uint32 limb vector (or Fe tuple) -> Python int."""
    if isinstance(limbs, tuple):
        limbs = np.stack([np.asarray(v) for v in limbs], axis=-1)
    arr = np.asarray(limbs, dtype=np.uint64)
    return sum(int(arr[..., i]) << (LIMB_BITS * i) for i in range(NLIMBS))


# --- whole-batch conversions (the vectorized host-prep substrate) ----------

def staging_out(out, bucket: int, cols: int, n: int) -> np.ndarray:
    """Validate (or allocate) a [bucket, cols] u16 staging buffer for a
    fused prepare_packed write — the one staging-buffer contract shared
    by the p256 and ed25519 packers."""
    if n > bucket:
        raise ValueError(f"batch {n} exceeds bucket {bucket}")
    if out is None:
        return np.empty((bucket, cols), np.uint16)
    if out.shape != (bucket, cols) or out.dtype != np.uint16:
        raise ValueError(
            f"staging buffer {out.shape}/{out.dtype} != "
            f"({bucket}, {cols})/uint16"
        )
    return out
#
# The 16-bit little-endian limb layout IS numpy's '<u2' byte layout, so a
# whole batch converts with one ``frombuffer`` over the concatenated
# little-endian int bytes — no per-limb Python.  The per-item
# ``to_limbs`` list comprehension costs ~2.5us/value; the batch form is
# ~50x cheaper per value at B=16384 and is what feeds the prepare_batch
# staging buffers (ops/p256.py, ops/ed25519.py).


def to_limbs_batch(vals) -> np.ndarray:
    """Iterable of B Python ints (each in [0, 2^256)) -> [B, 16] uint32."""
    vals = vals if isinstance(vals, (list, tuple)) else list(vals)
    if not vals:
        return np.zeros((0, NLIMBS), np.uint32)
    buf = b"".join([v.to_bytes(32, "little") for v in vals])
    return (
        np.frombuffer(buf, dtype="<u2")
        .reshape(len(vals), NLIMBS)
        .astype(np.uint32)
    )


def from_limbs_batch(rows) -> list:
    """[B, 16] limb rows (any int dtype, values < 2^16) -> list of B ints."""
    arr = np.ascontiguousarray(np.asarray(rows), dtype="<u2")
    return [int.from_bytes(row.tobytes(), "little") for row in arr]


def limb_words(rows: np.ndarray) -> np.ndarray:
    """[B, 16] limb rows (values < 2^16) -> [B, 4] '<u8' word view.

    The comparison helpers below scan words, not limbs — 4 column passes
    instead of 16.  Zero-copy when ``rows`` is already a contiguous u16
    array (e.g. a '<u2' view of prep staging bytes)."""
    rows = np.asarray(rows)
    if rows.dtype != np.dtype("<u2"):
        rows = rows.astype("<u2")
    return np.ascontiguousarray(rows).view("<u8")


def words_of(x: int) -> np.ndarray:
    """Host constant -> [4] '<u8' little-endian words (for words_lt)."""
    return np.frombuffer(x.to_bytes(32, "little"), dtype="<u8")


def words_lt(words: np.ndarray, bound_words: np.ndarray) -> np.ndarray:
    """Vectorized 256-bit compare over [B, 4] '<u8' words -> [B] bool.

    Lexicographic scan from the most-significant word down — 4 elementwise
    column passes, no per-item Python (this is how prepare_batch turns the
    r/s/coordinate range checks into array ops)."""
    lt = np.zeros(words.shape[0], np.bool_)
    decided = np.zeros(words.shape[0], np.bool_)
    for i in (3, 2, 1, 0):
        col = words[:, i]
        b = bound_words[i]
        lt |= ~decided & (col < b)
        decided |= col != b
    return lt


def limbs_lt(rows: np.ndarray, bound: int) -> np.ndarray:
    """Vectorized 256-bit compare: [B, 16] limb rows < bound -> [B] bool."""
    return words_lt(limb_words(rows), words_of(bound))


def limbs_is_zero(rows: np.ndarray) -> np.ndarray:
    """[B, 16] limb rows == 0 -> [B] bool (vectorized)."""
    return ~limb_words(rows).any(axis=1)


def limbs_add_const(rows: np.ndarray, c: int) -> np.ndarray:
    """(rows + c) mod 2^256 -> [B, 16] uint32, limbwise with vectorized
    carry propagation.

    Used for the ECDSA second x-candidate r2 = r + n: callers must gate on
    a no-overflow condition (e.g. r < p - n) — the mod-2^256 wrap is not
    meaningful arithmetic."""
    cl = to_limbs(c)
    rows = np.asarray(rows, dtype=np.uint32)
    out = np.empty_like(rows)
    carry = np.zeros(rows.shape[0], np.uint32)
    for i in range(NLIMBS):
        s = rows[:, i] + cl[i] + carry
        out[:, i] = s & MASK
        carry = s >> np.uint32(LIMB_BITS)
    return out


def fe_from_array(x: jnp.ndarray) -> Fe:
    """[..., 16] uint32 array -> limb tuple (unstack the trailing axis)."""
    return tuple(x[..., i] for i in range(NLIMBS))


def fe_to_array(a: Fe) -> jnp.ndarray:
    """Limb tuple -> [..., 16] uint32 array."""
    return jnp.stack(a, axis=-1)


def fe_const(x: int) -> Tuple[np.uint32, ...]:
    """Host constant as a tuple of uint32 scalars (broadcasts under vmap)."""
    return tuple(np.uint32(int(v)) for v in to_limbs(x))


@dataclasses.dataclass(frozen=True)
class FieldSpec:
    """Constants for Montgomery arithmetic mod a fixed 256-bit modulus.

    Built host-side once per field (P-256 coordinate field, P-256 group
    order, curve25519 field, ...) and closed over by the jitted kernels.
    """

    modulus_int: int
    modulus: Tuple[np.uint32, ...]
    m_prime: np.uint32  # -modulus^-1 mod 2^16
    r_mod: Tuple[np.uint32, ...]  # R mod m    (Montgomery one)
    r2_mod: Tuple[np.uint32, ...]  # R^2 mod m  (to-Montgomery factor)

    @staticmethod
    def make(modulus: int) -> "FieldSpec":
        r = 1 << BITS
        m_inv = pow(modulus, -1, 1 << LIMB_BITS)
        return FieldSpec(
            modulus_int=modulus,
            modulus=fe_const(modulus),
            m_prime=np.uint32((-m_inv) % (1 << LIMB_BITS)),
            r_mod=fe_const(r % modulus),
            r2_mod=fe_const((r * r) % modulus),
        )


# ---------------------------------------------------------------------------
# Elementwise helpers.


def fe_select(c: jnp.ndarray, a: Fe, b: Fe) -> Fe:
    """where(c, a, b) limbwise; c is a bool scalar ([B] under vmap)."""
    return tuple(jnp.where(c, x, y) for x, y in zip(a, b))


def fe_eq(a: Fe, b: Fe) -> jnp.ndarray:
    acc = a[0] == b[0]
    for i in range(1, NLIMBS):
        acc = acc & (a[i] == b[i])
    return acc


def fe_is_zero(a: Fe) -> jnp.ndarray:
    acc = a[0] == 0
    for i in range(1, NLIMBS):
        acc = acc & (a[i] == 0)
    return acc


def fe_zero() -> Fe:
    return tuple(jnp.uint32(0) for _ in range(NLIMBS))


def _cond_sub(m: Tuple[np.uint32, ...], t: list, t_hi: jnp.ndarray) -> Fe:
    """Given fully-carried t (16 limbs + small high part t_hi), return
    t - m if t >= m else t.  Branch-free."""
    borrow = jnp.uint32(0)
    d = []
    for j in range(NLIMBS):
        x = t[j] - m[j] - borrow
        borrow = (x >> np.uint32(31)) & np.uint32(1)
        d.append(x & MASK)
    ge = t_hi >= borrow  # high part absorbs the final borrow iff t >= m
    return tuple(jnp.where(ge, d[j], t[j]) for j in range(NLIMBS))


# ---------------------------------------------------------------------------
# Modular add/sub (inputs fully reduced < m, outputs fully reduced < m).


def add_mod(spec: FieldSpec, a: Fe, b: Fe) -> Fe:
    s = [a[j] + b[j] for j in range(NLIMBS)]
    carry = jnp.uint32(0)
    for j in range(NLIMBS):
        s[j] = s[j] + carry
        carry = s[j] >> LIMB_BITS
        s[j] = s[j] & MASK
    return _cond_sub(spec.modulus, s, carry)


def sub_mod(spec: FieldSpec, a: Fe, b: Fe) -> Fe:
    # a + m - b, then conditionally subtract m. a+m never underflows b.
    m = spec.modulus
    s = [a[j] + m[j] for j in range(NLIMBS)]
    carry = jnp.uint32(0)
    for j in range(NLIMBS):
        s[j] = s[j] + carry
        carry = s[j] >> LIMB_BITS
        s[j] = s[j] & MASK
    borrow = jnp.uint32(0)
    for j in range(NLIMBS):
        x = s[j] - b[j] - borrow
        borrow = (x >> np.uint32(31)) & np.uint32(1)
        s[j] = x & MASK
    return _cond_sub(spec.modulus, s, carry - borrow)


# ---------------------------------------------------------------------------
# Montgomery multiplication (lazy-carry CIOS).
#
# Three lowerings of the *same* arithmetic (measured trade-offs in
# ops/lowering.py):
#
# - ``block`` (TPU default): the outer CIOS loop as a 4-step ``lax.scan``
#   of 4 unrolled iterations each — fastest measured on v5e AND ~10x
#   cheaper to compile than full unrolling.
# - ``unrolled``: the 16-iteration loop fully unrolled at trace time into
#   one straight-line program.  XLA compile time explodes with basic-block
#   size (minutes for the full ladder graph), and on v5e the giant block
#   also schedules worse than ``block``.
# - ``scan``/``loop`` (CPU default): the outer loop as a 16-step
#   ``lax.scan`` (~70-op body).  Compiles instantly everywhere; the
#   per-step fusion barrier costs throughput on TPU.
#
# Dispatch is by backend at trace time, overridable with ``set_mode`` (the
# equivalence of the three lowerings is itself under test).


from .lowering import mode as _lowering_mode
from .lowering import set_mode as _set_lowering_mode


def set_mode(mode):
    """Force a lowering mode (None = auto: 'block' off-CPU, 'loop' on CPU).

    Deprecated alias for :func:`minbft_tpu.ops.lowering.set_mode` ('scan'
    maps to 'loop')."""
    _set_lowering_mode("loop" if mode == "scan" else mode)


def mont_mul(spec: FieldSpec, a: Fe, b: Fe) -> Fe:
    """Montgomery product a*b*R^-1 mod m (R = 2^256), result < m.

    Lazy carries: column accumulators grow by at most 4 * 2^16 per
    iteration (two product halves from a_i*b and two from u*m), so after 16
    iterations every accumulator is < 2^22 — uint32 never overflows and no
    intra-loop carry propagation is needed.  Column 0's low 16 bits are
    always exact (carries only flow upward), so the reduction quotient
    u = t0 * m' mod 2^16 is computed directly from the lazy accumulator.
    """
    m = _lowering_mode()
    if m == "unrolled":
        return _mont_mul_unrolled(spec, a, b)
    if m == "block":
        return _mont_mul_block(spec, a, b)
    return _mont_mul_scan(spec, a, b)


def _mont_mul_unrolled(spec: FieldSpec, a: Fe, b: Fe) -> Fe:
    m = spec.modulus
    mp = spec.m_prime
    t = [jnp.uint32(0)] * (NLIMBS + 2)
    for i in range(NLIMBS):
        ai = a[i]
        for j in range(NLIMBS):
            p = ai * b[j]  # exact: 16-bit x 16-bit in uint32
            t[j] = t[j] + (p & MASK)
            t[j + 1] = t[j + 1] + (p >> LIMB_BITS)
        u = ((t[0] & MASK) * mp) & MASK
        for j in range(NLIMBS):
            q = u * m[j]
            t[j] = t[j] + (q & MASK)
            t[j + 1] = t[j + 1] + (q >> LIMB_BITS)
        c0 = t[0] >> LIMB_BITS  # low 16 bits are zero by construction of u
        t = t[1:] + [jnp.uint32(0)]
        t[0] = t[0] + c0
    return _mont_finish(m, t)


def _mont_mul_scan(spec: FieldSpec, a: Fe, b: Fe) -> Fe:
    m = spec.modulus
    mp = spec.m_prime
    zero = jnp.zeros_like(b[0])

    def step(t, ai):
        t = list(t)
        for j in range(NLIMBS):
            p = ai * b[j]
            t[j] = t[j] + (p & MASK)
            t[j + 1] = t[j + 1] + (p >> LIMB_BITS)
        u = ((t[0] & MASK) * mp) & MASK
        for j in range(NLIMBS):
            q = u * m[j]
            t[j] = t[j] + (q & MASK)
            t[j + 1] = t[j + 1] + (q >> LIMB_BITS)
        c0 = t[0] >> LIMB_BITS
        t = t[1:] + [jnp.zeros_like(t[0])]
        t[0] = t[0] + c0
        return tuple(t), None

    t0 = (zero,) * (NLIMBS + 2)
    t, _ = lax.scan(step, t0, jnp.stack(a))
    return _mont_finish(m, list(t))


_BLOCK = 4


def _mont_mul_block(spec: FieldSpec, a: Fe, b: Fe) -> Fe:
    """CIOS with the outer loop as a 4-step ``lax.scan`` whose body unrolls
    4 iterations — same arithmetic as the other lowerings, ~4x smaller HLO
    than ``unrolled`` (faster compile) with 4x fewer fusion barriers than
    ``loop`` (better TPU throughput)."""
    m = spec.modulus
    mp = spec.m_prime
    zero = jnp.zeros_like(b[0] + jnp.uint32(0))

    # Stacking the limbs gives [16, ...] (scalar-shaped limbs under vmap,
    # or explicitly batched [B] limbs); the scan consumes rows of 4.
    a_arr = jnp.stack([jnp.asarray(x) + zero for x in a])
    a_blocks = a_arr.reshape((NLIMBS // _BLOCK, _BLOCK) + a_arr.shape[1:])

    def step(t, ablk):
        t = list(t)
        for k in range(_BLOCK):
            ai = ablk[k]
            for j in range(NLIMBS):
                p = ai * b[j]
                t[j] = t[j] + (p & MASK)
                t[j + 1] = t[j + 1] + (p >> LIMB_BITS)
            u = ((t[0] & MASK) * mp) & MASK
            for j in range(NLIMBS):
                q = u * m[j]
                t[j] = t[j] + (q & MASK)
                t[j + 1] = t[j + 1] + (q >> LIMB_BITS)
            c0 = t[0] >> LIMB_BITS
            t = t[1:] + [jnp.zeros_like(t[0])]
            t[0] = t[0] + c0
        return tuple(t), None

    t0 = (zero,) * (NLIMBS + 2)
    t, _ = lax.scan(step, t0, a_blocks)
    return _mont_finish(m, list(t))


def _mont_finish(m, t: list) -> Fe:
    # Single full carry pass, then one conditional subtract (result < 2m).
    for j in range(NLIMBS + 1):
        c = t[j] >> LIMB_BITS
        t[j] = t[j] & MASK
        t[j + 1] = t[j + 1] + c
    t_hi = t[NLIMBS] + (t[NLIMBS + 1] << LIMB_BITS)
    return _cond_sub(m, t[:NLIMBS], t_hi)


def mont_sqr(spec: FieldSpec, a: Fe) -> Fe:
    return mont_mul(spec, a, a)


def to_mont(spec: FieldSpec, a: Fe) -> Fe:
    """a -> a*R mod m."""
    return mont_mul(spec, a, spec.r2_mod)


def from_mont(spec: FieldSpec, a: Fe) -> Fe:
    """a*R -> a mod m (multiply by 1)."""
    one = fe_const(1)
    return mont_mul(spec, a, one)


def mont_one(spec: FieldSpec) -> Fe:
    return spec.r_mod


# ---------------------------------------------------------------------------
# Exponentiation / inversion.


def mont_pow_static(spec: FieldSpec, a: Fe, exponent: int) -> Fe:
    """a^exponent (Montgomery domain) for a *host-static* exponent.

    Square-and-select-multiply inside one ``fori_loop`` (256 iterations, two
    mont_mul call sites) — the ladder itself must stay a loop to keep the
    HLO small; only the field ops inside it are unrolled.
    """
    bits = np.array(
        [(exponent >> (BITS - 1 - i)) & 1 for i in range(BITS)], dtype=np.uint32
    )
    bits_d = jnp.asarray(bits)

    def body(i, acc):
        acc = mont_sqr(spec, acc)
        mul = mont_mul(spec, acc, a)
        return fe_select(bits_d[i] == 1, mul, acc)

    return lax.fori_loop(0, BITS, body, mont_one(spec))


def mont_inv(spec: FieldSpec, a: Fe) -> Fe:
    """Fermat inversion a^(m-2) — modulus must be prime."""
    return mont_pow_static(spec, a, spec.modulus_int - 2)




def batch_inv_host(vals, mod):
    """Host-side Montgomery batch inversion: one ``pow`` + 3(B-1) mults
    for B inverses (a host pow costs ~25us; a mult ~0.1us).  All vals
    must be nonzero.  Shared by the P-256/Ed25519 sign paths and the
    ECDSA verify prep (one s^-1 sweep per batch in p256.prepare_batch)."""
    n = len(vals)
    if n == 0:
        return []
    prefix = [1] * (n + 1)
    p = 1
    for i, v in enumerate(vals):
        p = p * v % mod
        prefix[i + 1] = p
    inv_total = pow(p, -1, mod)
    out = [0] * n
    for i in range(n - 1, -1, -1):
        out[i] = prefix[i] * inv_total % mod
        inv_total = inv_total * vals[i] % mod
    return out
