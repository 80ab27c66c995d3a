#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that minbft-tpu still starts on the chip.

Drives the device-crypto consensus path once, end to end, on one TPU chip,
through the entry points a user would call, and checks what comes out by
the repo's own means (host OpenSSL verdicts, the invariant checker, a
serial replay of the committed order).  It is a smoke test, not a
benchmark: every time or rate it prints is ONE reading of one run,
labelled with the device, for information only.

    python chip_smoke.py              # one chip: device, kernels, cluster, deployment
    python chip_smoke.py --chips 4    # four chips: the mesh and the engine pool, only

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``;
any failed phase makes it ``"ok": false`` and the exit code non-zero.  No
phase falls back to the CPU, and no phase's exception is passed over.

One process holds a chip at a time.  This parent never imports JAX: it runs
the phases one after another as child processes, each in its own process
group (killed when the phase ends, whatever happened), all sharing one
compile cache (``JAX_COMPILATION_CACHE_DIR`` where set, else
``<checkout>/.jax_cache`` — minbft_tpu/utils/jaxcache.py), and takes the
device description from the first child's output.  It sets no
``JAX_PLATFORMS`` for a phase that needs the chip.

Built from committed files only: the USIG here is the software one
(minbft_tpu/usig/software.py, keyspec SOFT_ECDSA), so the chip machine
needs no compiler for minbft_tpu/native; host crypto must be OpenSSL
(``cryptography``) — the pure-Python EC oracle in utils/hostcrypto.py is
for tests, and the smoke fails rather than take it.

Phases (each a function below; ``Size`` is their test-only size argument —
tests/test_chip_smoke.py runs them tiny on the CPU backend; the script
itself always runs ``FULL``):

  device      jax.devices(): the platform must be tpu
  kernels     the five main-path kernels at the served bucket (512, block
              lowering) through the engine's own jitted entry points,
              against host verdicts; fills the compile cache
  cluster     BASELINE.json config 3: n=7 f=3, ECDSA-P256 request/reply
              signatures and ECDSA USIG certificates, one BatchVerifier per
              replica exactly as `peer run` builds it, 2,000 pipelined
              writes from 16 clients + 200 fast reads
  deployment  `peer testnet` + four `peer run` processes over TCP, replica 0
              owning the chip; 100 requests through `peer request`;
              replica 0's /metrics through `peer metrics`
  multichip   (--chips 4 only) a 4,096-lane ECDSA batch over the mesh
              against the one-chip kernel, and a G=4 grouped cluster on an
              EnginePool(chips=4) against the same run with chips=1
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import hashlib
import json
import os
import random
import signal
import statistics
import struct
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Size:
    """What a phase runs at.  ``FULL`` is what the script runs; tests pass
    a tiny one (CPU backend, loop lowering, small bucket)."""

    platform: str = "tpu"  # what jax.devices()[0].platform must be
    lowering: str = "block"
    bucket: int = 512  # the served bucket: `peer run --batch` default
    n: int = 7
    f: int = 3
    clients: int = 16
    requests: int = 2000
    depth: int = 8
    reads: int = 200
    deploy_requests: int = 100
    mesh_lanes: int = 4096
    groups: int = 4
    group_requests: int = 400

    @property
    def on_cpu(self) -> bool:
        return self.platform == "cpu"


FULL = Size()


def say(msg: str) -> None:
    print(msg, flush=True)


def _setup_jax(size: Size):
    """Child-side set-up shared by the chip phases: the one compile cache,
    the lowering, and the device description (checked, never assumed)."""
    from minbft_tpu.ops import lowering
    from minbft_tpu.utils import hostcrypto, jaxcache

    check(
        hostcrypto._HAVE_OSSL,
        "utils/hostcrypto has no OpenSSL backend: the smoke path never "
        "takes the pure-Python EC oracle",
    )
    cache_dir = jaxcache.enable_compilation_cache()
    import jax

    devs = jax.devices()
    check(
        devs[0].platform == size.platform,
        f"JAX runs on {devs[0].platform}, not {size.platform}: "
        f"{[str(d) for d in devs]}",
    )
    lowering.set_mode(size.lowering)
    return jax, cache_dir


def _device_label(jax) -> str:
    d = jax.devices()[0]
    return f"{d.platform} {d.device_kind} x{len(jax.devices())}"


# ---------------------------------------------------------------------------
# phase: device


def phase_device(seed: int, size: Size, out: str) -> dict:
    import jax

    devs = jax.devices()
    say(f"device: jax {jax.__version__} sees {[str(d) for d in devs]}")
    check(
        devs[0].platform == size.platform,
        f"JAX found no {size.platform} (platform {devs[0].platform})",
    )
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
    }


# ---------------------------------------------------------------------------
# phase: kernels


class _Rng(random.Random):
    """random.Random with the one method hostcrypto.keygen asks of its rng."""

    def randbelow(self, n: int) -> int:
        return self.randrange(n)


def _seeded_inputs(seed: int, bucket: int):
    """ECDSA / HMAC / Ed25519 lanes from ``seed`` via utils/hostcrypto, a
    few corrupted, with the host verifier's verdict per lane."""
    import hmac as hmac_mod

    from minbft_tpu.utils import hostcrypto as hc

    rng = _Rng(seed)
    bad = set(rng.sample(range(bucket), max(2, bucket // 100)))

    keys = [hc.keygen(rng) for _ in range(4)]
    ecdsa = []
    for i in range(bucket):
        d, q = keys[i % len(keys)]
        digest = hashlib.sha256(b"smoke-ecdsa-%d-%d" % (seed, i)).digest()
        r, s = hc.ecdsa_sign(d, digest)
        if i in bad:
            # three kinds of wrong: another message, a bent s, r out of range
            kind = i % 3
            if kind == 0:
                digest = hashlib.sha256(digest).digest()
            elif kind == 1:
                s ^= 1 << rng.randrange(200)
            else:
                r = 0
        ecdsa.append((q, digest, (r, s)))
    ecdsa_want = [hc.ecdsa_verify(q, dg, sg) for q, dg, sg in ecdsa]

    macs = []
    for i in range(bucket):
        key = rng.randbytes(32)
        msg = rng.randbytes(32)
        mac = hmac_mod.new(key, msg, hashlib.sha256).digest()
        if i in bad:
            mac = bytes([mac[0] ^ 1]) + mac[1:]
        macs.append((key, msg, mac))
    mac_want = [
        hmac_mod.compare_digest(hmac_mod.new(k, m, hashlib.sha256).digest(), t)
        for k, m, t in macs
    ]

    ed_keys = [hc.ed25519_keygen(rng.randbytes(32)) for _ in range(4)]
    eds = []
    for i in range(bucket):
        sd, pub = ed_keys[i % len(ed_keys)]
        msg = b"smoke-ed25519-%d-%d" % (seed, i)
        sig = hc.ed25519_sign(sd, msg)
        if i in bad:
            if i % 2:
                msg += b"!"
            else:
                sig = sig[:40] + bytes([sig[40] ^ 4]) + sig[41:]
        eds.append((pub, msg, sig))
    ed_want = [hc.ed25519_verify(p, m, s) for p, m, s in eds]

    sign_items = [
        (keys[i % len(keys)][0],
         hashlib.sha256(b"smoke-sign-%d-%d" % (seed, i)).digest())
        for i in range(bucket)
    ]
    ed_sign_items = [
        (ed_keys[i % len(ed_keys)][0], b"smoke-edsign-%d-%d" % (seed, i))
        for i in range(bucket)
    ]
    return {
        "bad": sorted(bad), "keys": keys, "ed_keys": ed_keys,
        "ecdsa": (ecdsa, ecdsa_want), "hmac": (macs, mac_want),
        "ed25519": (eds, ed_want),
        "ecdsa_sign": sign_items, "ed25519_sign": ed_sign_items,
    }


class _CompileClock:
    """JAX's own monitoring events, summed between ``take()`` calls: how a
    first call's seconds split into tracing (Python), lowering, and the
    backend compile — which, on a persistent-cache hit, is the retrieval;
    and the executables that came from the kernel store instead, which
    traced, lowered and compiled nothing."""

    _PARTS = {
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
        "/jax/core/compile/backend_compile_duration": "compile_s",
    }

    def __enter__(self):
        from jax import monitoring

        self._reset()
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        from jax import monitoring

        monitoring.unregister_event_duration_listener(self._duration)
        monitoring.unregister_event_listener(self._event)

    def _reset(self):
        from minbft_tpu.utils import kernelstore

        self._secs = dict.fromkeys(self._PARTS.values(), 0.0)
        self._hits = 0
        self._store = kernelstore.totals()

    def _duration(self, event, secs, **_kw):
        part = self._PARTS.get(event)
        if part:
            self._secs[part] += secs

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._hits += 1

    def take(self) -> dict:
        from minbft_tpu.utils import kernelstore

        out = {k: round(v, 2) for k, v in self._secs.items()}
        out["cache_hits"] = self._hits
        # executables that came from the kernel store since the last take
        # (utils/kernelstore.py): those calls traced and compiled nothing
        now = kernelstore.totals()
        out["store_loads"] = now["loads"] - self._store["loads"]
        out["store_load_s"] = round(now["load_s"] - self._store["load_s"], 2)
        self._reset()
        return out


def _timed(fn, clock: _CompileClock, warm_runs: int = 5):
    """-> (first call: its seconds and their split, median warm
    milliseconds, last result).  ``fn`` ends in np.asarray."""
    clock.take()
    t0 = time.perf_counter()
    res = fn()
    first = dict(first_call_s=round(time.perf_counter() - t0, 2), **clock.take())
    warm = []
    for _ in range(warm_runs):
        t0 = time.perf_counter()
        res = fn()
        warm.append((time.perf_counter() - t0) * 1e3)
    return first, statistics.median(warm), res


def _first_call(first: dict) -> str:
    if first["store_loads"] and first["trace_s"] < 1:
        return (
            f"first call {first['first_call_s']:7.2f} s = loaded from the "
            f"kernel store in {first['store_load_s']:.1f} s"
        )
    return (
        f"first call {first['first_call_s']:7.2f} s = trace {first['trace_s']:.1f}"
        f" + lower {first['lower_s']:.1f} + compile {first['compile_s']:.1f} s"
        f" ({'cache hit' if first['cache_hits'] else 'compiled'})"
    )


def phase_kernels(seed: int, size: Size, out: str) -> dict:
    jax, cache_dir = _setup_jax(size)
    with _CompileClock() as clock:
        return _kernels(seed, size, jax, cache_dir, clock)


def _kernels(seed: int, size: Size, jax, cache_dir: str, clock) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from minbft_tpu.ops import ed25519 as ed
    from minbft_tpu.ops import p256
    from minbft_tpu.ops.hmac_sha256 import hmac_verify_kernel_packed
    from minbft_tpu.utils import hostcrypto as hc
    from minbft_tpu.utils import jaxcache

    b = size.bucket
    label = _device_label(jax)
    before = jaxcache.entry_count(cache_dir)
    say(f"kernels: bucket {b}, lowering {size.lowering}, on {label}; "
        f"compile cache {cache_dir} holds {before} entries")
    inp = _seeded_inputs(seed, b)
    say(f"kernels: inputs from seed {seed:#x}, {len(inp['bad'])} corrupted "
        f"lanes {inp['bad']}")
    on_device = set(jax.devices())
    report = {}

    def placed(name, arr):
        check(
            set(arr.devices()) <= on_device
            and all(d.platform == size.platform for d in arr.devices()),
            f"{name}: output sits on {arr.devices()}, not the {size.platform}",
        )

    def verify_case(name, packed, kernel, want):
        dev_in = None

        def run():
            nonlocal dev_in
            dev_in = kernel(jnp.asarray(packed))
            return np.asarray(dev_in)

        first, warm_ms, got = _timed(run, clock)
        placed(name, dev_in)
        got = [bool(x) for x in got]
        wrong = [i for i in range(b) if got[i] != want[i]]
        check(not wrong, f"{name}: lanes {wrong[:8]} differ from the host verifier")
        check(
            sum(want) == b - len(inp["bad"]),
            f"{name}: host accepted {sum(want)} of {b} "
            f"({len(inp['bad'])} corrupted)",
        )
        report[name] = dict(first, warm_ms=round(warm_ms, 3))
        say(f"kernels: {name:15s} {_first_call(first)}, warm {warm_ms:8.3f} "
            f"ms/dispatch of {b} lanes, {b} lanes equal the host verdicts "
            f"[one smoke run on {label}]")

    items, want = inp["ecdsa"]
    verify_case("ecdsa_verify", p256.prepare_packed(items, b),
                p256.ecdsa_verify_kernel_packed, want)

    items, want = inp["hmac"]
    rows = np.frombuffer(
        b"".join(k + m + t for k, m, t in items), dtype=">u4"
    ).reshape(b, 24).astype(np.uint32)
    verify_case("hmac_verify", rows, hmac_verify_kernel_packed, want)

    items, want = inp["ed25519"]
    verify_case("ed25519_verify", ed.prepare_packed(items, b),
                ed.ed25519_verify_kernel_packed, want)

    # Sign kernels: prepare -> fixed-base comb on the device -> finish,
    # the three stages of the engine's sign dispatch (sign_batch composes
    # them); every device signature must verify on the host.
    def sign_case(name, sign_batch, items, kernel, prepare, host_ok):
        first, warm_ms, sigs = _timed(lambda: sign_batch(items, bucket=b), clock)
        placed(name, kernel(prepare(items, b)[0]))
        wrong = [i for i, sig in enumerate(sigs) if not host_ok(i, sig)]
        check(len(sigs) == b and not wrong,
              f"{name}: device signatures {wrong[:8]} fail the host verifier")
        report[name] = dict(first, warm_ms=round(warm_ms, 3))
        say(f"kernels: {name:15s} {_first_call(first)}, warm {warm_ms:8.3f} "
            f"ms/batch of {b} (host halves included), {b} signatures verify "
            f"on the host [one smoke run on {label}]")

    pubs = {d: q for d, q in inp["keys"]}
    items = inp["ecdsa_sign"]
    sign_case(
        "ecdsa_sign", p256.sign_batch, items, p256.ecdsa_kg_kernel,
        p256.sign_prepare,
        lambda i, sig: hc.ecdsa_verify(pubs[items[i][0]], items[i][1], sig),
    )
    ed_pubs = {sd: pub for sd, pub in inp["ed_keys"]}
    ed_items = inp["ed25519_sign"]
    sign_case(
        "ed25519_sign", ed.sign_batch, ed_items, ed.ed25519_rb_kernel,
        ed.sign_prepare,
        lambda i, sig: hc.ed25519_verify(ed_pubs[ed_items[i][0]], ed_items[i][1], sig)
        and sig == hc.ed25519_sign(*ed_items[i]),
    )

    after = jaxcache.entry_count(cache_dir)
    say(f"kernels: compile cache entries {before} -> {after}")
    return {"kernels": report, "cache_before": before, "cache_after": after}


# ---------------------------------------------------------------------------
# phase: cluster (the main path)


def _engine_counts(engine) -> dict:
    v = engine.stats.get("ecdsa_p256")
    s = engine.sign_stats.get("ecdsa_p256")
    return {
        "verify_items": v.items if v else 0,
        "verify_batches": v.batches if v else 0,
        "verify_padded": v.padded_lanes if v else 0,
        "verify_timeouts": v.dispatch_timeouts if v else 0,
        "table_hits": v.key_table_hits if v else 0,
        "table_builds": v.key_table_builds if v else 0,
        "sign_items": s.items if s else 0,
        "sign_timeouts": s.dispatch_timeouts if s else 0,
        "sign_fallback": s.host_fallback_items if s else 0,
    }


def check_engine_on_device(name: str, engine, base: dict) -> dict:
    """The device did this engine's work: protocol items through both
    ECDSA queues since ``base``, and the liveness net never fired."""
    now = _engine_counts(engine)
    d = {k: now[k] - base.get(k, 0) for k in now}
    check(d["verify_items"] > 0, f"{name}: no ecdsa_p256 verify reached the device queue")
    check(d["sign_items"] > 0, f"{name}: no ECDSA sign reached the device queue")
    check(
        now["verify_timeouts"] == 0 and now["sign_timeouts"] == 0,
        f"{name}: dispatch timeouts (verify {now['verify_timeouts']}, "
        f"sign {now['sign_timeouts']}): items were re-run on the host",
    )
    check(now["sign_fallback"] == 0,
          f"{name}: {now['sign_fallback']} signatures fell back to the host")
    check(d["table_builds"] == 0 and d["table_hits"] > 0,
          f"{name}: {d['table_builds']} comb tables built while serving "
          f"({d['table_hits']} hits): the key store's keys were not primed")
    check(not engine.written_off(),
          f"{name}: device written off for {engine.written_off()}")
    return d


async def _drive(clients, per_client: int, depth: int, op_for, timeout: float):
    """Each client pipelines gather-windows of ``depth`` (the `peer bench`
    shape).  -> ([(op, result)], [latency ms], wall seconds)."""
    accepted, lat_ms = [], []

    async def one(client, k):
        op = op_for(client.client_id, k)
        t0 = time.perf_counter()
        res = await asyncio.wait_for(client.request(op), timeout)
        lat_ms.append((time.perf_counter() - t0) * 1e3)
        accepted.append((op, res))

    async def drive(client):
        for k0 in range(0, per_client, depth):
            await asyncio.gather(
                *[one(client, k) for k in range(k0, min(k0 + depth, per_client))]
            )

    t0 = time.perf_counter()
    await asyncio.gather(*[drive(c) for c in clients])
    return accepted, lat_ms, time.perf_counter() - t0


async def _serial_replay(ledger) -> tuple:
    """The plain reference: the committed payload order through a fresh
    SimpleLedger, one after the other.  -> (head digest, {payload: digest})."""
    from minbft_tpu.sample.requestconsumer import SimpleLedger

    ref = SimpleLedger()
    digests = {}
    for h in range(1, ledger.length + 1):
        payload = ledger.block(h).payload
        digests[payload] = await ref.deliver(payload)
    return ref.state_digest(), digests


async def _converged(ledgers, want: int, timeout: float = 60.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline and not all(
        lg.length >= want for lg in ledgers
    ):
        await asyncio.sleep(0.05)


async def _cluster(seed: int, size: Size, label: str) -> dict:
    from minbft_tpu import api
    from minbft_tpu.client import new_client
    from minbft_tpu.sample.authentication import generate_testnet_keys
    from minbft_tpu.sample.config import SimpleConfiger
    from minbft_tpu.sample.conn.inprocess import InProcessClientConnector
    from minbft_tpu.sample.peer.placement import start_local_cluster
    from minbft_tpu.testing import InvariantChecker

    asyncio.get_running_loop().set_task_factory(asyncio.eager_task_factory)
    n, f = size.n, size.f
    rng = random.Random(seed)
    store = generate_testnet_keys(n, n_clients=size.clients, usig_spec="SOFT_ECDSA")
    cfg = SimpleConfiger(n=n, f=f, timeout_request=60.0, timeout_prepare=30.0)

    t0 = time.perf_counter()
    cluster = await start_local_cluster(
        store, cfg, batch=size.bucket, on_cpu=size.on_cpu
    )
    say(f"cluster: n={n} f={f}, {cluster.placement}; {n} engines warmed in "
        f"{time.perf_counter() - t0:.1f} s")
    check(all(e is not None for e in cluster.engines),
          f"cluster: a replica chose host crypto: {cluster.placement}")
    clients = []
    try:
        for c in range(size.clients):
            client = new_client(
                c, n, f, store.client_authenticator(c),
                InProcessClientConnector(cluster.stubs),
                retransmit_interval=30.0,
            )
            await client.start()
            clients.append(client)

        # One committed request off the clock (first-contact USIG epochs),
        # then count protocol traffic only.
        warm_op = b"smoke-warmup"
        accepted = [(warm_op, await asyncio.wait_for(clients[0].request(warm_op), 120))]
        base = [_engine_counts(e) for e in cluster.engines]

        per_client = -(-size.requests // size.clients)
        salt = {c: rng.randbytes(8).hex().encode() for c in range(size.clients)}
        writes, lat_ms, wall = await _drive(
            clients, per_client, size.depth,
            lambda cid, k: b"smoke-%d-%d-%s" % (cid, k, salt[cid]), 240,
        )
        accepted += writes
        total = len(accepted)
        check(len(writes) == per_client * size.clients >= size.requests,
              f"cluster: {len(writes)} writes acknowledged of {size.requests}")

        await _converged(cluster.ledgers, total)
        heads = {(lg.length, lg.state_digest()) for lg in cluster.ledgers}
        check(
            heads == {(total, cluster.ledgers[0].state_digest())},
            f"cluster: ledgers disagree: {[lg.length for lg in cluster.ledgers]}",
        )

        # Fast reads: all n replicas must answer alike, no ordered fallback.
        want_head = struct.pack(">Q", total) + cluster.ledgers[0].state_digest()

        async def read(client):
            try:
                return await asyncio.wait_for(
                    client.request(b"head", read_only=True,
                                   read_fallback=False, read_timeout=30.0),
                    60,
                )
            except (asyncio.TimeoutError, api.ReadOnlyQueryError) as e:
                raise SmokeFailure(f"cluster: fast read failed: {e!r}") from e

        reads = []
        for k0 in range(0, size.reads, len(clients)):
            reads += await asyncio.gather(
                *[read(clients[k % len(clients)])
                  for k in range(k0, min(k0 + len(clients), size.reads))]
            )
        check(len(reads) == size.reads and all(r == want_head for r in reads),
              "cluster: a fast read disagrees with the committed head")

        # The checker's committed-results pass is quadratic in the run, so
        # it gets every tenth result; EVERY result is held to the serial
        # replay just below.
        InvariantChecker(cluster.replicas, cluster.ledgers).check(accepted[::10])
        ref_head, ref_digests = await _serial_replay(cluster.ledgers[0])
        check(ref_head == cluster.ledgers[0].state_digest(),
              "cluster: serial replay of the committed order gives another head")
        wrong = [op for op, res in accepted if ref_digests.get(op) != res]
        check(not wrong, f"cluster: {len(wrong)} acknowledged results differ "
              f"from the serial replay, e.g. {wrong[:2]}")

        deltas = [
            check_engine_on_device(f"cluster: replica {i} engine", e, base[i])
            for i, e in enumerate(cluster.engines)
        ]
        views = [r.metrics.current_view for r in cluster.replicas]
        check(not any(views), f"cluster: view changes in a fault-free run: views {views}")
    finally:
        for client in clients:
            await client.stop()
        await cluster.stop()

    lat = sorted(lat_ms)
    items = sum(d["verify_items"] for d in deltas)
    batches = sum(d["verify_batches"] for d in deltas)
    padded = sum(d["verify_padded"] for d in deltas)
    out = {
        "requests": len(writes), "reads": len(reads),
        "req_per_s": round(len(writes) / wall, 1),
        "p50_ms": round(lat[len(lat) // 2], 1),
        "p99_ms": round(lat[min(len(lat) - 1, int(len(lat) * 0.99))], 1),
        "verify_items": items, "sign_items": sum(d["sign_items"] for d in deltas),
        "mean_batch": round(items / max(batches, 1), 2),
        "padded_share": round(padded / max(padded + items, 1), 4),
    }
    say(f"cluster: {out['requests']} writes + {out['reads']} fast reads "
        f"acknowledged by f+1={f + 1} (reads: all {n}) matching replies; {n} "
        f"ledgers at height {total} agree with the serial replay; invariants "
        f"green")
    say(f"cluster: committed {out['req_per_s']} req/s, p50 {out['p50_ms']} ms, "
        f"p99 {out['p99_ms']} ms ({size.clients} clients x depth {size.depth}); "
        f"{n} engines: {items} device verifies, {out['sign_items']} device "
        f"signs, mean verify batch {out['mean_batch']}, padded-lane share "
        f"{out['padded_share']}; 0 dispatch timeouts, 0 host-fallback items "
        f"[one smoke run on {label}, not a benchmark]")
    return out


def phase_cluster(seed: int, size: Size, out: str) -> dict:
    jax, _ = _setup_jax(size)
    return asyncio.run(_cluster(seed, size, _device_label(jax)))


# ---------------------------------------------------------------------------
# phase: deployment (process per replica; this phase's own process needs no JAX)


def check_device_metrics(fams: dict) -> dict:
    """Replica 0's exposition (obs/prom.py engine families): ECDSA items
    went through the device verify and sign queues, no dispatch timed out."""

    def samples(name):
        return fams.get(name, {}).get("samples", {})

    def ecdsa(name):
        return sum(
            v for key, v in samples(name).items()
            if dict(key).get("queue") == "ecdsa_p256"
        )

    verify_items = ecdsa("minbft_verify_queue_items_total")
    sign_items = ecdsa("minbft_sign_queue_items_total")
    check(verify_items > 0, "deployment: /metrics shows no device ECDSA verify items")
    check(sign_items > 0, "deployment: /metrics shows no device ECDSA sign items")
    for side in ("verify", "sign"):
        name = f"minbft_{side}_queue_dispatch_timeouts_total"
        check(name in fams, f"deployment: /metrics lacks {name}")
        timeouts = sum(samples(name).values())
        check(timeouts == 0, f"deployment: {name} = {timeouts}")
    return {"verify_items": int(verify_items), "sign_items": int(sign_items)}


def _maps_libtpu(pid: int) -> bool:
    with open(f"/proc/{pid}/maps") as fh:
        return "libtpu" in fh.read()


def phase_deployment(seed: int, size: Size, out: str) -> dict:
    import re
    import shutil

    from minbft_tpu.obs.prom import parse_exposition
    from minbft_tpu.sample.requestconsumer import SimpleLedger
    from minbft_tpu.utils import jaxcache
    from minbft_tpu.utils.netports import free_base_port, wait_ports

    n = 4
    d = os.path.join(out, "testnet")
    shutil.rmtree(d, ignore_errors=True)
    os.makedirs(d)
    pypath = REPO + os.pathsep + os.environ.get("PYTHONPATH", "")
    timeouts = {"CONSENSUS_TIMEOUT_REQUEST": "60s", "CONSENSUS_TIMEOUT_PREPARE": "30s"}
    # One chip, one owning process: replica 0 gets the environment as the
    # machine gives it; everything else is pinned to the CPU platform and
    # never loads the TPU library.
    env_chip = dict(os.environ, PYTHONPATH=pypath, **timeouts)
    env_cpu = dict(env_chip, JAX_PLATFORMS="cpu")
    peer = [sys.executable, "-m", "minbft_tpu.sample.peer"]
    cache_dir = jaxcache.cache_dir()
    before = jaxcache.entry_count(cache_dir)

    base_port = free_base_port(n)
    scaffold = subprocess.run(
        peer + ["testnet", "-n", str(n), "--usig", "SOFT_ECDSA", "-d", d,
                "--base-port", str(base_port)],
        env=env_cpu, cwd=REPO, capture_output=True, text=True, timeout=120,
    )
    check(scaffold.returncode == 0, f"deployment: scaffold failed: {scaffold.stderr[-800:]}")

    procs, logs = [], []
    try:
        for i in range(n):
            log = open(os.path.join(d, f"replica{i}.log"), "wb")
            logs.append(log)
            cmd = peer + [
                "--keys", os.path.join(d, f"keys.replica{i}.yaml"),
                "--config", os.path.join(d, "consensus.yaml"),
                "--transport", "tcp", "run", str(i), "--metrics-port", "0",
            ]
            if i:
                cmd.append("--no-batch")
            procs.append(subprocess.Popen(
                cmd, env=env_cpu if i else env_chip, cwd=REPO,
                stdout=subprocess.DEVNULL, stderr=log,
            ))
        # replica 0 listens only once its engine is warm (kernels traced
        # and loaded from the cache the earlier phases filled)
        up = wait_ports([base_port + i for i in range(n)], timeout=300)
        dead = [i for i, p in enumerate(procs) if p.poll() is not None]
        check(up and not dead, f"deployment: replicas never bound (exited: {dead}); "
              f"replica 0 log: {_tail(os.path.join(d, 'replica0.log'))}")

        ops = [b"deploy-%d-%d" % (seed, k) for k in range(size.deploy_requests)]
        t0 = time.perf_counter()
        req = subprocess.run(
            peer + ["--keys", os.path.join(d, "keys.yaml"),
                    "--config", os.path.join(d, "consensus.yaml"),
                    "--transport", "tcp", "request", "--timeout", "120"],
            input=b"\n".join(ops) + b"\n", env=env_cpu, cwd=REPO,
            capture_output=True, timeout=600,
        )
        wall = time.perf_counter() - t0
        results = req.stdout.decode().split()
        check(req.returncode == 0 and len(results) == len(ops),
              f"deployment: {len(results)} of {len(ops)} requests committed "
              f"(rc {req.returncode}): {req.stderr.decode()[-800:]}")

        async def replay():
            ref = SimpleLedger()
            return [(await ref.deliver(op)).hex() for op in ops]

        check(results == asyncio.run(replay()),
              "deployment: results differ from the serial replay of the same operations")

        log0 = _tail(os.path.join(d, "replica0.log"), 1 << 20)
        line = re.search(r"replica 0 crypto: (.*)", log0)
        check(line is not None, "deployment: replica 0 never said where its crypto runs")
        say(f"deployment: replica 0 crypto: {line.group(1)}")
        check(
            line.group(1).startswith(f"device engine on {size.platform}"),
            f"deployment: replica 0 runs `peer run` without a {size.platform} "
            f"device engine: {line.group(1)}",
        )
        for i in range(n):
            check("entered view" not in _tail(os.path.join(d, f"replica{i}.log"), 1 << 20),
                  f"deployment: replica {i} changed view in a fault-free run")
        for i in range(1, n):
            check(not _maps_libtpu(procs[i].pid),
                  f"deployment: --no-batch replica {i} loaded the TPU library")

        m = re.search(r"metrics on http://[^:]+:(\d+)/metrics", log0)
        check(m is not None, "deployment: replica 0 announced no metrics endpoint")
        scrape = subprocess.run(
            peer + ["metrics", f"127.0.0.1:{m.group(1)}"],
            env=env_cpu, cwd=REPO, capture_output=True, text=True, timeout=60,
        )
        check(scrape.returncode == 0, f"deployment: `peer metrics` failed: {scrape.stderr[-400:]}")
        with open(os.path.join(d, "replica0.metrics.txt"), "w") as fh:
            fh.write(scrape.stdout)
        counts = check_device_metrics(parse_exposition(scrape.stdout))
    finally:
        for p in procs:
            if p.poll() is None:
                p.terminate()
        for p in procs:
            try:
                p.wait(20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()
        for log in logs:
            log.close()

    after = jaxcache.entry_count(cache_dir)
    say(f"deployment: compile cache entries before {before}, after {after}")
    check(after == before or before == 0,
          f"deployment: `peer run` compiled {after - before} kernels that the "
          f"earlier phases had not left in the cache")
    say(f"deployment: {len(ops)} requests committed over TCP through 4 `peer "
        f"run` processes in {wall:.1f} s (serial `peer request`, process "
        f"start included); replica 0 /metrics: {counts['verify_items']} "
        f"device ECDSA verifies, {counts['sign_items']} device signs, 0 "
        f"dispatch timeouts; replicas 1-3 (--no-batch) never loaded the TPU "
        f"library [one smoke run]")
    return dict(counts, requests=len(ops), cache_before=before, cache_after=after)


def _tail(path: str, limit: int = 2000) -> str:
    try:
        with open(path, "rb") as fh:
            return fh.read()[-limit:].decode(errors="replace")
    except OSError as e:
        return f"({e})"


# ---------------------------------------------------------------------------
# phase: multichip (--chips 4)


async def _mesh_batch(seed: int, size: Size, jax, devices, label: str) -> dict:
    import jax.numpy as jnp
    import numpy as np

    from minbft_tpu.ops import p256
    from minbft_tpu.parallel import BatchVerifier
    from minbft_tpu.parallel import mesh as mesh_mod
    from minbft_tpu.utils import hostcrypto as hc

    lanes, b = size.mesh_lanes, size.bucket
    rng = _Rng(seed)
    d, q = hc.keygen(rng)
    bad = set(rng.sample(range(lanes), max(2, lanes // 100)))
    items = []
    for i in range(lanes):
        digest = hashlib.sha256(b"smoke-mesh-%d-%d" % (seed, i)).digest()
        r, s = hc.ecdsa_sign(d, digest)
        items.append((q, digest, (r, s ^ 2 if i in bad else s)))
    want = [hc.ecdsa_verify(*it) for it in items]
    packed = p256.prepare_packed(items, lanes)

    mesh = mesh_mod.make_mesh(devices)
    engine = BatchVerifier(max_batch=lanes, buckets=(lanes,), mesh=mesh)
    check(engine.mesh is not None and engine.mesh.size == len(devices),
          "multichip: the engine dropped its mesh")
    kernel = engine._sharded("ecdsa", mesh_mod.sharded_ecdsa_kernel)

    def through_mesh():
        t0 = time.perf_counter()
        sharded = kernel(packed)
        got = [bool(x) for x in np.asarray(sharded)]
        return sharded, got, time.perf_counter() - t0

    def through_one_chip():
        got = []
        for c0 in range(0, lanes, b):
            got += [bool(x) for x in np.asarray(
                p256.ecdsa_verify_kernel_packed(jnp.asarray(packed[c0:c0 + b])))]
        return got

    # Side by side: the two compiles overlap (tracing is Python and takes
    # turns), and every chip-second here is charged four times.
    (sharded, got_mesh, first), one_chip = await asyncio.gather(
        asyncio.to_thread(through_mesh), asyncio.to_thread(through_one_chip)
    )
    shard_devs = {s.device for s in sharded.addressable_shards}
    check(shard_devs == set(devices),
          f"multichip: output shards sit on {shard_devs}, not on {devices}")
    wrong = [i for i in range(lanes) if got_mesh[i] != one_chip[i]]
    check(not wrong, f"multichip: mesh and one-chip kernel differ at lanes {wrong[:8]}")
    check(one_chip == want, "multichip: one-chip kernel differs from the host verifier")

    # and the same batch through the engine's public surface
    via_engine = await engine.verify_ecdsa_p256_many(items)
    check([bool(x) for x in via_engine] == want,
          "multichip: BatchVerifier(mesh=...) verdicts differ from the host verifier")
    check(engine.stats["ecdsa_p256"].dispatch_timeouts == 0 and not engine.written_off(),
          "multichip: the mesh engine's liveness net fired")
    say(f"multichip (a): {lanes} ECDSA lanes over a {len(devices)}-device mesh "
        f"equal the one-chip kernel ({lanes // b} x {b}) and the host verdicts "
        f"lane by lane ({len(bad)} corrupted); output shards on "
        f"{sorted(str(x) for x in shard_devs)}; first sharded call {first:.1f} s "
        f"[one smoke run on {label}]")
    return {"lanes": lanes, "shard_devices": len(shard_devs)}


async def _grouped_run(seed: int, size: Size, chips: int, devices, store) -> dict:
    """A G-group n=4 f=1 in-process cluster, one EnginePool(chips=...) per
    replica as `peer run --groups G --chips C` builds it, one serial
    client per group (so the committed order, and with it the ledger
    digest, is the same in every run)."""
    from minbft_tpu.groups import MultiGroupClient, new_group_runtime
    from minbft_tpu.parallel import EnginePool
    from minbft_tpu.sample.config import SimpleConfiger
    from minbft_tpu.sample.conn.inprocess import (
        InProcessClientConnector,
        InProcessPeerConnector,
        make_testnet_stubs,
    )
    from minbft_tpu.sample.peer.placement import (
        prime_key_tables,
        replica_authenticator,
        warm_engines,
    )
    from minbft_tpu.sample.requestconsumer import SimpleLedger

    n, f, G = 4, 1, size.groups
    cfg = SimpleConfiger(n=n, f=f, timeout_request=60.0, timeout_prepare=30.0, groups=G)
    stubs = make_testnet_stubs(n)
    ledgers = [[SimpleLedger() for _ in range(G)] for _ in range(n)]
    pools, runtimes = [], []
    for i in range(n):
        pool = EnginePool(
            chips=chips, devices=list(devices) if chips > 1 else None,
            max_batch=size.bucket, buckets=(size.bucket,),
            **({"sign_on_device": True} if size.on_cpu else {}),
        )
        pools.append(pool)
        rt = new_group_runtime(
            i, cfg,
            [replica_authenticator(store, i, None, True) for _ in range(G)],
            InProcessPeerConnector(stubs), ledgers[i], engine_pool=pool,
        )
        stubs[i].assign_replica(rt)
        runtimes.append(rt)
    prime_key_tables(store)  # as `peer run` does, before the engines warm
    await warm_engines([eng for pool in pools for eng in pool.engines])
    mem_base = [
        (dev.memory_stats() or {}).get("peak_bytes_in_use") for dev in devices[:chips]
    ]
    for rt in runtimes:
        await rt.start()
    client = MultiGroupClient(
        0, n, f, G, store.client_authenticator(0),
        InProcessClientConnector(stubs), retransmit_interval=30.0,
    )
    await client.start()
    try:
        base = [[_engine_counts(e) for e in pool.engines] for pool in pools]

        async def group_client(g):
            out = []
            for k in range(size.group_requests):
                op = b"smoke-g%d-%d-%d" % (g, k, seed)
                out.append((op, await asyncio.wait_for(client.request(op, group=g), 120)))
            return out

        t0 = time.perf_counter()
        accepted = await asyncio.gather(*[group_client(g) for g in range(G)])
        wall = time.perf_counter() - t0
        for g in range(G):
            await _converged([ledgers[i][g] for i in range(n)], size.group_requests)
        digests = []
        for g in range(G):
            heads = {(ledgers[i][g].length, ledgers[i][g].state_digest()) for i in range(n)}
            check(len(heads) == 1 and ledgers[0][g].length == size.group_requests,
                  f"multichip: group {g} ledgers disagree at chips={chips}: {heads}")
            ref_head, ref_digests = await _serial_replay(ledgers[0][g])
            check(ref_head == ledgers[0][g].state_digest()
                  and all(ref_digests.get(op) == res for op, res in accepted[g]),
                  f"multichip: group {g} differs from its serial replay at chips={chips}")
            digests.append(ledgers[0][g].state_digest().hex())
        deltas = [
            [check_engine_on_device(
                f"multichip: replica {i} chip {c} engine (chips={chips})", e, base[i][c])
             for c, e in enumerate(pool.engines)]
            for i, pool in enumerate(pools)
        ]
    finally:
        await client.stop()
        for rt in runtimes:
            await rt.stop()
    return {
        "digests": digests, "placement": pools[0].placement(), "pools": pools,
        "wall": wall, "mem_base": mem_base,
        "verify_per_chip": [sum(deltas[i][c]["verify_items"] for i in range(n))
                            for c in range(len(pools[0].engines))],
    }


async def _multichip(seed: int, size: Size, jax, label: str) -> dict:
    import jax.numpy as jnp

    from minbft_tpu.ops import p256
    from minbft_tpu.sample.authentication import generate_testnet_keys

    asyncio.get_running_loop().set_task_factory(asyncio.eager_task_factory)
    devices = jax.devices()[:4]
    check(len(devices) == 4, f"multichip: {len(jax.devices())} devices, need 4")
    out = {"mesh": await _mesh_batch(seed, size, jax, devices, label)}

    G = size.groups
    store = generate_testnet_keys(4, n_clients=1, usig_spec="SOFT_ECDSA")
    one = await _grouped_run(seed, size, 1, devices, store)
    four = await _grouped_run(seed, size, 4, devices, store)
    check(four["placement"] == {g: g % 4 for g in range(G)},
          f"multichip: placement {four['placement']}")
    check(one["digests"] == four["digests"],
          f"multichip: per-group ledger digests differ between chips=1 "
          f"{one['digests']} and chips=4 {four['digests']}")

    # Evidence that chip 0 alone could not produce: every home engine's
    # own device queue did work (checked in _grouped_run), a kernel
    # dispatched under each engine's placement scope on a worker thread
    # — as the engine dispatches — commits its output to that engine's
    # device, and each device's allocator saw it.
    probe = p256.prepare_packed([((0, 0), b"\x00" * 32, (0, 0))], size.bucket)

    def scoped(eng):
        with eng._device_scope():
            return p256.ecdsa_verify_kernel_packed(jnp.asarray(probe)).devices()

    pool = four["pools"][0]
    homes = []
    for c, eng in enumerate(pool.engines):
        on = await asyncio.to_thread(scoped, eng)
        check(on == {devices[c]} == {eng.device},
              f"multichip: chip {c}'s engine dispatches to {on}, not {devices[c]}")
        homes.append(str(devices[c]))
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use") for dev in devices]
    if all(p is not None for p in peaks):
        check(all(p > 0 for p in peaks),
              f"multichip: a device's allocator never held anything: {peaks}")
        mem = f"peak device bytes {peaks} (before the pooled run {four['mem_base']})"
    else:
        mem = "memory_stats(): not reported by this backend"
    say(f"multichip (b): G={G} n=4 f=1, {size.group_requests} requests per group: "
        f"EnginePool(chips=4) placement {four['placement']}, per-group ledger "
        f"digests equal the chips=1 run and the serial replay; device verifies "
        f"per home chip {four['verify_per_chip']} (chips=1: "
        f"{one['verify_per_chip']}); engines dispatch to {homes}; {mem}; wall "
        f"{four['wall']:.1f} s at chips=4, {one['wall']:.1f} s at chips=1 "
        f"[one smoke run on {label}, serial clients, not a benchmark]")
    out["pool"] = {"placement": {str(k): v for k, v in four["placement"].items()},
                   "verify_per_chip": four["verify_per_chip"], "homes": homes}
    return out


def phase_multichip(seed: int, size: Size, out: str) -> dict:
    jax, _ = _setup_jax(size)
    return asyncio.run(_multichip(seed, size, jax, _device_label(jax)))


# ---------------------------------------------------------------------------
# parent: phases as child processes, one holder of the chip at a time

PHASES = {
    "device": (phase_device, 180),
    "kernels": (phase_kernels, 700),
    "cluster": (phase_cluster, 500),
    "deployment": (phase_deployment, 400),
    "multichip": (phase_multichip, 2400),  # --chips 4 only: the builder's run
}
TOTAL_BUDGET_S = 1150  # the default run's 1200 s, less the time to report


def _run_phase_child(name: str, seed: int, out: str) -> int:
    """Child side: run one phase at FULL size, leave its result in
    ``<out>/<phase>.json``.  A failed check or any exception is a
    non-zero exit with its traceback — never caught and passed over."""
    fn, _limit = PHASES[name]
    result = fn(seed, FULL, out)
    with open(os.path.join(out, f"{name}.json"), "w") as fh:
        json.dump(result, fh)
    return 0


def _spawn_phase(name: str, seed: int, out: str, limit: float):
    """Parent side: one phase in its own process group, killed (the whole
    group: `peer run` grandchildren included) when the phase ends."""
    result_path = os.path.join(out, f"{name}.json")
    if os.path.exists(result_path):
        os.remove(result_path)
    t0 = time.monotonic()
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--phase", name,
         "--seed", str(seed), "--out", out],
        cwd=REPO, start_new_session=True,
    )
    try:
        rc = child.wait(timeout=limit)
    except subprocess.TimeoutExpired:
        rc = None
    finally:
        try:
            os.killpg(child.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        child.wait()
    took = time.monotonic() - t0
    if rc is None:
        say(f"chip_smoke: phase {name} exceeded its {limit:.0f} s and was killed")
        return None, took
    if rc != 0 or not os.path.exists(result_path):
        say(f"chip_smoke: phase {name} FAILED (exit code {rc}) after {took:.0f} s")
        return None, took
    with open(result_path) as fh:
        result = json.load(fh)
    say(f"chip_smoke: phase {name} ok in {took:.0f} s")
    return result, took


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh and engine-pool paths, on four chips")
    ap.add_argument("--seed", type=lambda s: int(s, 0), default=0x5EED,
                    help="seed of every generated input (keys of the clusters' "
                    "keystores excepted: those come from the OS)")
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out", "chip_smoke"),
                    help="directory for phase results and the deployment scaffold")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    out = os.path.abspath(args.out)
    os.makedirs(out, exist_ok=True)
    if args.phase:
        return _run_phase_child(args.phase, args.seed, out)

    names = ["device", "multichip"] if args.chips == 4 else [
        "device", "kernels", "cluster", "deployment"
    ]
    started = time.monotonic()
    device, failed = None, None
    budget = TOTAL_BUDGET_S if args.chips == 1 else 2 * TOTAL_BUDGET_S
    for name in names:
        left = budget - (time.monotonic() - started)
        result, _took = _spawn_phase(
            name, args.seed, out, max(1.0, min(PHASES[name][1], left))
        )
        if result is None:
            failed = name
            break
        if name == "device":
            device = result
            if device["count"] < args.chips:
                say(f"chip_smoke: --chips {args.chips} but JAX sees {device['count']}")
                failed = name
                break
    ok = failed is None
    if not ok:
        say(f"chip_smoke: FAILED in phase {failed}")
    line = {"ok": ok, "device": device}
    if not ok:
        line["failed"] = failed
    print(json.dumps(line), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
