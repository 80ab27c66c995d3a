"""Where a replica's crypto runs — the one placement rule.

``peer run``, ``peer selftest`` and ``chip_smoke.py`` all build their
engines, authenticators and in-process clusters here, so that what the
smoke test proves on the chip is what a deployed replica does.

The rule: a replica owns one :class:`~minbft_tpu.parallel.BatchVerifier`
(replicas are mutually distrusting machines — they never share an engine
or its verdict memo), with one padded bucket of ``batch`` lanes, wherever
JAX found an accelerator; USIG certificates, REQUEST/REPLY signature
checks and REPLY signing then all ride it.  Host crypto is what is left
when the operator said ``--no-batch`` or JAX runs on the CPU backend —
and the choice is always reported, never silent.
"""

from __future__ import annotations

import dataclasses
import gc
import os
from typing import List, Optional, Tuple

# The collector's thresholds of a serving replica (settle_collector), from
# every pass timed on the chip machine's host with 128 writes in flight
# (PERF.md section 6, PR 27).  No pass of any generation collected
# anything there: what the protocol allocates dies by reference count, so
# every pass is overhead and its cost is the objects it walks, 0.4-0.9 us
# each.
# - 50,000 allocations net of deallocations before a youngest pass.  At
#   CPython's 700 a block of 128 commits (~400 tracked objects a write in
#   flight, ~50,000 in all) trips it 105-180 times a second, and with the
#   middle passes that follow that is 4.5-6.5 % of the loop, and every
#   write in flight is promoted (~117,000 objects a second into the oldest
#   generation).  Above the writes in flight only what stays is counted:
#   the heap's own growth, 7,000-10,000 objects a second, so a pass comes
#   every 2-6 s, walks at most 50,000 objects and holds the loop 35-50 ms
#   (about 1 %).  25,000 still trips on the blocks (2.6 % at n=7);
#   100,000 holds the loop 77-89 ms a pass for the same rate.  This is the
#   floor; collector_thresholds() raises it with the replicas one process
#   carries.
# - a middle pass after 3 youngest ones, not 11: it walks what those
#   promoted, 50-145 ms every 6-20 s (under 1 %); after 11 it would walk
#   half a million objects at once.
# - 10 middle passes before a full one, CPython's own: with promotions down
#   to the heap's growth that is minutes apart, and after it the
#   interpreter's quarter rule (a full pass only once a quarter of the
#   oldest generation is new) holds full passes to 4 x (cost an object) x
#   (growth a second), under 2 % at any heap size.
COLLECTOR_THRESHOLDS = (50_000, 2, 10)

# What one replica holds in flight, twice over (PERF.md section 6, PR 29).
# 128 writes in flight are ~7,000 tracked objects a replica of the process:
# 25,000 trips on the blocks at n=7 and 50,000 does not (PR 27); at n=31
# 50,000 trips 1.9 times a second, middle and full passes follow, and the
# collector holds the loop 11.5 % of a window; 200,000 still trips on some
# blocks, 400,000 and 800,000 on none.  Twice what is in flight leaves the
# first threshold to the heap's growth alone, as the floor does at n <= 3.
YOUNGEST_PER_REPLICA = 14_000


def collector_thresholds(replicas: int) -> Tuple[int, int, int]:
    """:data:`COLLECTOR_THRESHOLDS` with the first raised to what
    ``replicas`` replicas in this process hold in flight, twice over."""
    youngest, middle, oldest = COLLECTOR_THRESHOLDS
    return max(youngest, YOUNGEST_PER_REPLICA * replicas), middle, oldest


def replica_engine(
    batch: int = 512, no_batch: bool = False, on_cpu: bool = False
) -> Tuple[Optional[object], str]:
    """-> ``(engine or None, one line saying which was chosen and why)``.

    ``on_cpu`` builds the engine on the CPU backend too, device sign lane
    included — for tests that run the device path's code at a tiny bucket
    where there is no chip; no entry point passes it."""
    if no_batch:
        return None, "host crypto (--no-batch)"
    import jax

    from ...utils.jaxcache import record_jax_events

    record_jax_events()  # before the warm-up traces the kernels
    backend = jax.default_backend()
    if backend == "cpu" and not on_cpu:
        if os.environ.get("JAX_PLATFORMS", "").lower() == "cpu":
            why = "JAX_PLATFORMS=cpu"
        else:
            why = "JAX found no accelerator and runs on the cpu backend"
        return None, f"host crypto ({why})"
    from ...parallel import BatchVerifier

    dev = jax.devices()[0]
    engine = BatchVerifier(
        max_batch=batch,
        buckets=(batch,),
        sign_on_device=True if backend == "cpu" else None,
    )
    return engine, (
        f"device engine on {dev.platform} ({dev.device_kind}), one "
        f"{batch}-lane bucket: UI, request and reply signatures batch on "
        f"the device"
    )


def replica_authenticator(
    store, replica_id: int, engine, device: bool, mac: bool = False
):
    """Replica ``replica_id``'s authenticator over ``engine``.  ``device``
    says whether per-message signatures (or MACs) batch on the device; it
    is separate from ``engine`` because a grouped runtime binds each
    group's home-chip engine later (``engine=None, device=True``)."""
    if mac:
        return store.mac_replica_authenticator(
            replica_id, engine=engine, device_macs=device
        )
    return store.replica_authenticator(
        replica_id, engine=engine, batch_signatures=device
    )


def device_schemes(store, mac: bool = False) -> Tuple[str, ...]:
    """The engine queues a replica built from ``store`` dispatches to the
    device: its USIG's certificate scheme and its message scheme."""
    usig = "hmac_sha256" if store.usig_spec == "HMAC_SHA256" else "ecdsa_p256"
    msg = "hmac_sha256" if mac else store.scheme.replace("-", "_")
    return tuple(sorted({usig, msg} & {"ecdsa_p256", "ed25519", "hmac_sha256"}))


def prime_key_tables(store) -> None:
    """Build the ECDSA verify kernel's comb table of every P-256 key
    ``store`` names (ops/p256.py: ~10 ms and 64 KiB a key, cached for the
    process), where an engine is built for it and before it serves: no
    build then falls inside a request (an unprimed key is served once by a
    host scalar multiplication and built on its second use).  Only the
    store's keys can reach the ECDSA queue (the authenticator verifies
    under the store's key for the claimed id, and a UI under the store's
    USIG anchor), so after this a replica builds none."""
    from ...ops import p256

    p256.prime_key_tables(store.ecdsa_p256_points())


async def warm_engine(engine, schemes=("ecdsa_p256",)) -> None:
    """One item signed and verified through each of ``engine``'s queues
    in ``schemes``, at the engine's bucket, BEFORE any protocol timer
    runs: inside a first request a kernel's load would be a prepare
    timeout and a view change against a replica that is merely starting
    up.  First the kernels' executables are loaded from the kernel store
    on the calling thread (:meth:`BatchVerifier.load_kernels`), where the
    runtime loads them 5-6 times faster than on the dispatchers' worker
    threads: ~4 s for the two ECDSA kernels on the TPU, during which the
    event loop is blocked, before ``start()``, before the listener binds
    and before any timer is armed.  Then the items go through the queues
    as in service, so the liveness net, the counters and the dispatch
    ring see each queue's first dispatch.  A kernel the store does not
    hold (a tree's first process; the CPU backend, which has no store)
    is traced and compiled by its queue's first dispatch, inside the
    net's first-dispatch allowance (parallel/engine.py), as before."""
    import hashlib
    import hmac

    from ...utils import hostcrypto as hc

    engine.load_kernels(schemes)
    msg = b"minbft-tpu engine warm-up"
    digest = hashlib.sha256(msg).digest()
    ok = True
    if "ecdsa_p256" in schemes:
        d, q = hc.keygen()
        sig = await engine.sign_ecdsa_p256(d, digest)
        ok &= await engine.verify_ecdsa_p256(q, digest, sig)
    if "ed25519" in schemes:
        seed, pub = hc.ed25519_keygen()
        ok &= await engine.verify_ed25519(
            pub, msg, await engine.sign_ed25519(seed, msg)
        )
    if "hmac_sha256" in schemes:
        mac = hmac.new(digest, digest, hashlib.sha256).digest()
        ok &= await engine.verify_hmac_sha256(digest, digest, mac)
    if not ok:
        raise RuntimeError("engine warm-up: the device rejected a valid item")


async def warm_engines(engines, schemes=("ecdsa_p256",), replicas: int = 1) -> None:
    """:func:`warm_engine` over several engines.  An executable belongs
    to its device: engines that share one warm one after the other (the
    first obtains it, the rest reuse it), engines on distinct devices
    side by side, but for their loads, which take the calling thread one
    device after another.  Warm-up ends with
    :func:`settle_collector` for the ``replicas`` this process carries,
    with engines or with none."""
    import asyncio

    by_device: dict = {}
    for engine in engines:
        by_device.setdefault(engine.device, []).append(engine)

    async def one_after_the_other(sharing):
        for engine in sharing:
            await warm_engine(engine, schemes)

    await asyncio.gather(*[one_after_the_other(g) for g in by_device.values()])
    settle_collector(replicas)


def settle_collector(replicas: int = 1) -> None:
    """The collector's policy of a replica about to serve, set where
    warm-up ends (:func:`warm_engines`' last act, so ``peer run``, the
    in-process clusters and the pool phase all get it from one place,
    before ``start()`` and before the listener binds: the one full pass it
    costs is off every protocol timer).

    Tracing the kernels leaves ~700,000 tracked objects alive for the life
    of the process (jaxprs, lowered modules, the imports), and CPython
    walks all of them in every full pass: 0.35-0.44 s a pass, 12-15 passes
    in 20 s of service.  ``gc.freeze()`` moves them to the permanent
    generation, which no pass walks; the ``gc.collect()`` before it keeps
    garbage out of there, and the one after it (over an empty heap, under
    1 ms) resets the interpreter's count of long-lived objects, which the
    quarter rule would otherwise still take from the frozen heap.  Then
    :func:`collector_thresholds` of the ``replicas`` this process carries
    (one in ``peer run``, all n in an in-process cluster).  The collector
    stays on and keeps its own accounting; nothing here reads a
    configuration.  A second call only freezes what has been allocated
    since."""
    gc.collect()
    gc.freeze()
    gc.collect()
    gc.set_threshold(*collector_thresholds(replicas))


@dataclasses.dataclass
class LocalCluster:
    replicas: List[object]
    ledgers: List[object]
    engines: List[Optional[object]]  # one per replica; None = host crypto
    stubs: List[object]
    placement: str  # replica_engine's line (the same for every replica)

    async def stop(self) -> None:
        for r in self.replicas:
            await r.stop()


async def start_local_cluster(
    store,
    cfg,
    batch: int = 512,
    no_batch: bool = False,
    on_cpu: bool = False,
    wrap_conn=None,
    opts=(),
) -> LocalCluster:
    """Start ``cfg.n`` in-process replicas over the in-process connector,
    each with its own engine by :func:`replica_engine` and a fresh
    ``SimpleLedger``, every engine warmed off the clock.
    ``wrap_conn(connector, endpoint)`` wraps each peer connector (fault
    injection).  Caller stops the cluster."""
    from ...core import new_replica
    from ...sample.conn.inprocess import (
        InProcessPeerConnector,
        make_testnet_stubs,
    )
    from ...sample.requestconsumer import SimpleLedger

    n = cfg.n
    stubs = make_testnet_stubs(n)
    ledgers = [SimpleLedger() for _ in range(n)]
    engines, replicas = [], []
    placement = ""
    for i in range(n):
        engine, placement = replica_engine(batch, no_batch, on_cpu)
        conn = InProcessPeerConnector(stubs)
        if wrap_conn is not None:
            conn = wrap_conn(conn, f"r{i}")
        r = new_replica(
            i,
            cfg,
            replica_authenticator(store, i, engine, engine is not None),
            conn,
            ledgers[i],
            opts=list(opts),
        )
        stubs[i].assign_replica(r)
        engines.append(engine)
        replicas.append(r)
    schemes = device_schemes(store)
    if "ecdsa_p256" in schemes and any(e is not None for e in engines):
        prime_key_tables(store)
    await warm_engines(
        [e for e in engines if e is not None], schemes, replicas=n
    )
    for r in replicas:
        await r.start()
    return LocalCluster(replicas, ledgers, engines, stubs, placement)
