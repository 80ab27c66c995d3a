"""Device-mesh sharding for the batch verification kernels.

The reference scales by adding replicas connected over gRPC (reference
sample/conn/grpc/); its crypto cost grows linearly and stays on each
replica's CPU.  Here the batch-verification workload is data-parallel by
construction, so scaling across TPU chips is a sharding annotation, not a
communication protocol: place the batch axis over a 1-D ``Mesh`` and XLA
partitions the kernel, with any cross-chip reduction (e.g. the "whole
quorum valid" conjunction) riding ICI collectives.

BASELINE config[4] (n=31, batch=1024, v4-8) maps to ``sharded_verifier``
with an 8-device mesh: 128 lanes per chip, one fused program per chip, one
all-reduce for aggregate statistics.
"""

from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

BATCH_AXIS = "batch"


def make_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """1-D device mesh over the batch axis.

    Defaults to all visible devices; pass an explicit device list (e.g. a
    CPU-backend virtual 8-device set in tests / ``dryrun_multichip``)."""
    if devices is None:
        devices = jax.devices()
    return Mesh(np.array(devices), (BATCH_AXIS,))


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) axis across the mesh."""
    return NamedSharding(mesh, P(BATCH_AXIS))


def round_up_to_mesh(mesh: Mesh, n: int) -> int:
    """Smallest multiple of the mesh size >= n.

    The batch-axis divisibility contract for every sharded kernel here:
    bucket ladders AND the engine's staging buffers must pad to THIS (the
    engine rounds its buckets through it at construction), or jit raises a
    sharding error at dispatch time."""
    sz = mesh.size
    return -(-n // sz) * sz


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def sharded_verifier(scalar_verify: Callable, mesh: Mesh, n_args: int):
    """vmap a scalar-shaped kernel and jit it with the batch axis sharded
    over ``mesh``.

    ``scalar_verify``: per-item kernel (limb/word arrays in; any output
    whose leading axis is the batch — bools for the verifiers, limb
    arrays for the sign kernel; trailing dims are replicated).
    ``n_args``: number of positional array arguments (all batch-leading).

    The result expects every argument's leading dimension to be a multiple
    of the mesh size (the engine's bucket sizes guarantee this).

    Per-lowering-mode jit (like the single-chip kernel entry points): the
    mode is read at trace time, so one jit instance would silently reuse
    whichever mode compiled first at a given shape.
    """
    sh = batch_sharding(mesh)
    batched = jax.vmap(scalar_verify)

    def build():
        return jax.jit(
            batched,
            in_shardings=(sh,) * n_args,
            out_shardings=sh,
        )

    import threading

    cache = {}
    lock = threading.Lock()  # callers dispatch from worker threads

    def wrapper(*args):
        from ..ops import lowering

        m = lowering.mode()
        with lock:
            fn = cache.get(m)
            if fn is None:
                fn = build()
                cache[m] = fn
        return fn(*args)

    return wrapper


def sharded_ecdsa_kernel(mesh: Mesh):
    """Batched ECDSA-P256 verify sharded across ``mesh`` — packed
    single-upload form ([B, PACKED_COLS] u16, see
    :func:`minbft_tpu.ops.p256.prepare_packed`).  The kernel's batch axis
    is explicit (it runs its two combs as one chain over 2B chain-lanes),
    so each device runs the whole kernel on its own B/mesh lanes under
    ``shard_map``: lanes are independent, nothing crosses chips."""
    from ..ops import lowering, p256

    return lowering.per_mode_jit(
        jax.shard_map(
            p256._verify_one_packed,
            mesh=mesh,
            in_specs=P(BATCH_AXIS),
            out_specs=P(BATCH_AXIS),
            # the field code starts its accumulators from constants, which
            # the varying-axes typing would call replicated
            check_vma=False,
        ),
        # sharded by the jit itself, over arguments that arrive unplaced:
        # not an executable of one device, so not the kernel store's
        store=False,
    )


def hmac_row_verify(row):
    """Scalar-shaped HMAC-SHA256 verify of one packed [24] u32 row
    (key | msg | mac) — the body the sharded kernel vmaps."""
    from ..ops import hmac_sha256 as hs

    return hs.hmac32_verify(row[0:8], row[8:16], row[16:24])


def sharded_hmac_kernel(mesh: Mesh):
    """Batched HMAC-SHA256 verify sharded across ``mesh`` (packed
    [B, 24] u32 rows)."""
    return sharded_verifier(hmac_row_verify, mesh, 1)


def sharded_ed25519_kernel(mesh: Mesh):
    """Batched Ed25519 verify sharded across ``mesh`` — packed
    single-upload form (see :func:`minbft_tpu.ops.ed25519.pack_arrays`)."""
    from ..ops import ed25519 as ed

    return sharded_verifier(ed._ed25519_verify_one_packed, mesh, 1)


def sharded_ecdsa_sign_kernel(mesh: Mesh):
    """Batched fixed-base k*G (the device half of ECDSA signing,
    :func:`minbft_tpu.ops.p256.sign_batch`) sharded across ``mesh``:
    takes [B, 16] nonce limbs, returns [B, 2, 16] X/Z limbs (uint16).
    Uses the fixed-base comb kernel; its precomputed table is a
    compile-time constant replicated on every device."""
    import jax.numpy as jnp

    from ..ops import p256

    table = jnp.asarray(p256._COMB_TABLE_NP)

    def kg_one(k):
        return p256._kg_comb_one(k.astype(jnp.uint32), table)

    return sharded_verifier(kg_one, mesh, 1)


def sharded_ed25519_sign_kernel(mesh: Mesh):
    """Batched fixed-base r*B (the device half of Ed25519 signing,
    :func:`minbft_tpu.ops.ed25519.sign_batch`) sharded across ``mesh``:
    [B, 16] nonce limbs in, [B, 3, 16] X/Y/Z limbs (uint16) out; the
    comb table replicates as a compile-time constant per device."""
    import jax.numpy as jnp

    from ..ops import ed25519 as ed

    table = jnp.asarray(ed._comb_table_np())

    def rb_one(r):
        return ed._rb_comb_one(r.astype(jnp.uint32), table)

    return sharded_verifier(rb_one, mesh, 1)
