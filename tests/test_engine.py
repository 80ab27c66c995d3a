"""Batching engine tests: adaptive flush, bucket padding, correctness of
batched lane results, and the engine-wired cluster (the submit-batch-then-
resolve restructuring of the reference's serial verification)."""

import asyncio
import hashlib
import hmac as hmac_mod
import time

from minbft_tpu.parallel import BatchVerifier


def _hmac_item(i: int, valid: bool = True):
    key = hashlib.sha256(b"key-%d" % i).digest()
    msg = hashlib.sha256(b"msg-%d" % i).digest()
    mac = hmac_mod.new(key, msg, hashlib.sha256).digest()
    if not valid:
        mac = bytes([mac[0] ^ 1]) + mac[1:]
    return key, msg, mac


def test_single_item_flushes_on_timeout():
    async def run():
        eng = BatchVerifier(max_batch=64, max_delay=0.01)
        ok = await eng.verify_hmac_sha256(*_hmac_item(0))
        assert ok
        st = eng.stats["hmac_sha256"]
        assert st.batches == 1 and st.items == 1
        return eng

    asyncio.run(run())


def test_concurrent_items_coalesce_and_resolve_lanes():
    async def run():
        eng = BatchVerifier(max_batch=64, max_delay=0.01)
        tasks = [
            asyncio.create_task(eng.verify_hmac_sha256(*_hmac_item(i, valid=(i % 3 != 0))))
            for i in range(20)
        ]
        results = await asyncio.gather(*tasks)
        for i, ok in enumerate(results):
            assert ok == (i % 3 != 0), f"lane {i}"
        st = eng.stats["hmac_sha256"]
        assert st.items == 20
        # All 20 should coalesce into few batches (typically 1).
        assert st.batches <= 3

    asyncio.run(run())


def test_full_batch_flushes_immediately():
    async def run():
        eng = BatchVerifier(max_batch=8, max_delay=10.0)  # long delay: only
        # a full batch can flush it quickly
        tasks = [
            asyncio.create_task(eng.verify_hmac_sha256(*_hmac_item(i)))
            for i in range(8)
        ]
        done = await asyncio.wait_for(asyncio.gather(*tasks), timeout=5)
        assert all(done)

    asyncio.run(run())


def test_cluster_with_batching_engine():
    """n=3 cluster where every replica routes verification through its own
    BatchVerifier (HMAC USIG; CPU SIM mode)."""
    from minbft_tpu.client import new_client
    from minbft_tpu.core import new_replica
    from minbft_tpu.sample.authentication import new_test_authenticators
    from minbft_tpu.sample.config import SimpleConfiger
    from minbft_tpu.sample.conn.inprocess import (
        InProcessClientConnector,
        InProcessPeerConnector,
        make_testnet_stubs,
    )
    from minbft_tpu.sample.requestconsumer import SimpleLedger

    async def run():
        n, f = 3, 1
        engines = [BatchVerifier(max_batch=32, max_delay=0.005) for _ in range(n)]
        configer = SimpleConfiger(n=n, f=f, timeout_request=30.0, timeout_prepare=15.0)
        replica_auths, client_auths = new_test_authenticators(
            n, n_clients=1, usig_kind="hmac", engines=engines,
            batch_signatures=False,  # only the USIG path batches on CPU SIM
        )
        stubs = make_testnet_stubs(n)
        ledgers = [SimpleLedger() for _ in range(n)]
        replicas = []
        for i in range(n):
            r = new_replica(
                i, configer, replica_auths[i], InProcessPeerConnector(stubs), ledgers[i]
            )
            stubs[i].assign_replica(r)
            replicas.append(r)
        for r in replicas:
            await r.start()
        client = new_client(
            0, n, f, client_auths[0], InProcessClientConnector(stubs), seq_start=0
        )
        await client.start()
        for k in range(3):
            await asyncio.wait_for(client.request(b"op-%d" % k), timeout=30)
        for _ in range(100):
            if all(lg.length == 3 for lg in ledgers):
                break
            await asyncio.sleep(0.05)
        await client.stop()
        for r in replicas:
            await r.stop()
        assert all(lg.length == 3 for lg in ledgers)
        # the engines actually batched something
        total = sum(
            st.items for e in engines for st in e.stats.values()
        )
        assert total > 0

    asyncio.run(run())


def test_hung_device_dispatch_falls_back_to_host():
    """A device dispatch that hangs (a device fault: the kernel call
    never returns) must not wedge the verification queue: after
    dispatch_timeout the items
    are re-verified on host, and repeated hangs write the device off so
    later batches skip the wait entirely."""
    import asyncio
    import threading

    from minbft_tpu.parallel.engine import BatchVerifier

    async def scenario():
        engine = BatchVerifier(max_batch=8, dispatch_timeout=0.2)
        hang = threading.Event()

        def hanging_dispatch(items):
            hang.wait(30)  # simulates a dispatch that never returns
            raise AssertionError("unreachable in test")

        import numpy as np

        def host_fallback(items):
            return np.array([item == b"good" for item in items], dtype=bool)

        engine._host_fallback_for = lambda name: host_fallback
        q = engine._queue("ecdsa_p256", hanging_dispatch)

        good = asyncio.ensure_future(q.submit(b"good"))
        bad = asyncio.ensure_future(q.submit(b"bad"))
        ok, nok = await asyncio.wait_for(asyncio.gather(good, bad), 10)
        assert ok is True and nok is False
        assert q.stats.dispatch_timeouts == 1

        # two more hangs -> the device is written off; a later batch goes
        # straight to host (no 0.2s wait — assert by elapsed time)
        for _ in range(2):
            await asyncio.wait_for(q.submit(b"good-%d" % _), 10)
        assert q._device_written_off
        t0 = asyncio.get_running_loop().time()
        assert await asyncio.wait_for(q.submit(b"good"), 10) is True
        # memo hit or host path; either way well under the device timeout
        assert asyncio.get_running_loop().time() - t0 < 0.15
        hang.set()  # let the abandoned threads exit
        return True

    assert asyncio.run(scenario())


def test_garbage_flood_does_not_evict_good_verdicts():
    """Round-4 verdict weak #7: failed verdicts live in their own small
    LRU, so a flood of distinct garbage signatures cannot evict known-good
    verdicts from the memo and re-drive device traffic for them."""

    async def scenario():
        eng = BatchVerifier(max_batch=64, max_delay=0.0)
        good = _hmac_item(0)
        assert await eng.verify_hmac_sha256(*good) is True
        q = eng._queues["hmac_sha256"]
        flood = q._NEG_MEMO_CAP + 200
        bads = [_hmac_item(10_000 + i, valid=False) for i in range(flood)]
        results = await asyncio.gather(
            *[eng.verify_hmac_sha256(*b) for b in bads]
        )
        assert not any(results)
        # the flood stayed out of the positive memo and its own LRU is
        # bounded; the good verdict survived
        assert len(q._neg_memo) <= q._NEG_MEMO_CAP
        assert q._memo == {good: True}
        hits_before = q.stats.memo_hits
        assert await eng.verify_hmac_sha256(*good) is True
        assert q.stats.memo_hits == hits_before + 1, "good verdict re-verified"
        return True

    assert asyncio.run(scenario())


def test_written_off_device_reprobes_and_recovers():
    """ADVICE r4: the dispatch-hang write-off is not permanent — after the
    re-probe window one batch re-tries the device and restores the queue
    when it answers again."""
    import threading

    import numpy as np

    async def scenario():
        engine = BatchVerifier(max_batch=8, dispatch_timeout=0.05)
        healthy = threading.Event()

        def flaky_dispatch(items):
            if not healthy.is_set():
                healthy.wait(30)  # hung device until healed
            return np.array([True] * len(items), dtype=bool)

        engine._host_fallback_for = (
            lambda name: lambda items: np.array([True] * len(items), bool)
        )
        q = engine._queue("ecdsa_p256", flaky_dispatch)
        q._REPROBE_AFTER = 0.3

        for i in range(3):
            assert await asyncio.wait_for(q.submit(b"it-%d" % i), 10) is True
        assert q._device_written_off

        healthy.set()  # device heals while written off
        await asyncio.sleep(0.35)  # past the re-probe window
        # the live batch resolves immediately via the fallback; the probe
        # runs out-of-band and restores the device shortly after
        t0 = asyncio.get_running_loop().time()
        assert await asyncio.wait_for(q.submit(b"probe"), 10) is True
        assert asyncio.get_running_loop().time() - t0 < 2.0, (
            "live batch waited on the probe"
        )
        for _ in range(100):
            if not q._device_written_off:
                break
            await asyncio.sleep(0.05)
        assert not q._device_written_off, "re-probe did not restore device"
        assert q._device_ever_succeeded
        return True

    assert asyncio.run(scenario())


def test_first_dispatch_gets_cold_compile_headroom():
    """ADVICE r4: a slow-but-healthy FIRST dispatch (cold kernel compile)
    must not count as a hang — the first-dispatch timeout is stretched,
    and only post-success dispatches run on the base timeout."""
    import numpy as np

    async def scenario():
        engine = BatchVerifier(max_batch=8, dispatch_timeout=0.15)

        def slow_dispatch(items):
            time.sleep(0.3)  # longer than base, within 4x headroom
            return np.array([True] * len(items), dtype=bool)

        engine._host_fallback_for = (
            lambda name: lambda items: np.array([False] * len(items), bool)
        )
        q = engine._queue("ecdsa_p256", slow_dispatch)
        # device verdict (True), NOT the fallback (False): no timeout fired
        assert await asyncio.wait_for(q.submit(b"cold"), 10) is True
        assert q.stats.dispatch_timeouts == 0
        assert q._device_ever_succeeded
        return True

    assert asyncio.run(scenario())


def test_host_prep_time_populated_for_device_schemes():
    """Round-6 prep/device split: every device dispatch accounts its host
    prep (pack) time separately, so host_prep_time_s is non-zero whenever
    a batch went through a device queue (the benchmark reads it as
    hostprep.ms_per_commit)."""

    async def run():
        eng = BatchVerifier(max_batch=8, buckets=(8,))
        assert await eng.verify_hmac_sha256(*_hmac_item(0))
        st = eng.stats["hmac_sha256"]
        assert st.host_prep_time_s > 0.0
        assert st.device_time_s > 0.0
        # prep is a sub-interval of the dispatch the device clock wraps
        assert st.host_prep_time_s <= st.device_time_s * 1.5 + 0.05

    asyncio.run(run())


def test_key_table_hits_and_builds_are_counted_and_exported():
    """The ECDSA queue's per-key comb tables (ops/p256.py): an unprimed
    key's second use inside a dispatch is one build, every later item
    under it a hit; both are in ``engine.stats``, in the Prometheus
    families next to ``host_prep_seconds_total`` and in the
    MINBFT_TRACE_DUMP engine doc."""
    from minbft_tpu.obs import critpath, prom
    from minbft_tpu.ops import p256
    from minbft_tpu.utils import hostcrypto as hc

    d, q = hc.keygen()
    digests = [hashlib.sha256(b"tab-%d" % i).digest() for i in range(5)]
    sigs = [hc.ecdsa_sign(d, dg) for dg in digests]

    async def run():
        eng = BatchVerifier(max_batch=8, buckets=(8,))
        assert await eng.verify_ecdsa_p256(q, digests[0], sigs[0])  # first use
        st = eng.stats["ecdsa_p256"]
        assert (st.key_table_hits, st.key_table_builds) == (0, 0)
        assert await eng.verify_ecdsa_p256(q, digests[1], sigs[1])  # second: built
        assert (st.key_table_hits, st.key_table_builds) == (0, 1)
        assert 0.0 < st.key_table_build_s <= st.host_prep_time_s
        oks = await asyncio.gather(
            *[eng.verify_ecdsa_p256(q, dg, sg) for dg, sg in zip(digests[2:], sigs[2:])]
        )
        assert all(oks)
        assert (st.key_table_hits, st.key_table_builds) == (3, 1)
        return eng

    p256._KEY_TABLES.clear()
    eng = asyncio.run(run())
    text = prom.render_families(prom._collect_engine(eng, {"replica": "0"}))
    assert 'minbft_verify_queue_key_table_hits_total{queue="ecdsa_p256",replica="0"} 3' in text
    assert 'minbft_verify_queue_key_table_builds_total{queue="ecdsa_p256",replica="0"} 1' in text
    assert "minbft_verify_queue_key_table_build_seconds_total" in text
    assert "minbft_sign_queue_key_table" not in text
    doc = critpath.engine_queue_doc(eng)
    assert doc["key_tables"]["ecdsa_p256"]["hits"] == 3
    assert doc["key_tables"]["ecdsa_p256"]["builds"] == 1
    # the one item before the build was the key's first use: one host
    # scalar multiplication, counted and timed beside the builds
    st = eng.stats["ecdsa_p256"]
    assert st.key_table_first_uses == 1
    assert 0.0 < st.key_table_first_use_s <= st.host_prep_time_s
    assert 'minbft_verify_queue_key_table_first_uses_total{queue="ecdsa_p256",replica="0"} 1' in text
    assert "minbft_verify_queue_key_table_first_use_seconds_total" in text
    assert doc["key_tables"]["ecdsa_p256"]["first_uses"] == 1
    assert doc["key_tables"]["ecdsa_p256"]["first_use_s"] == st.key_table_first_use_s


def test_padded_lane_accounting_is_thread_safe():
    """Regression pin for the padded_lanes data race: dispatchers run on
    worker threads (up to max_inflight concurrently) and used to do a bare
    read-modify-write on the shared stats counter — two racing dispatches
    could lose an increment.  All padded-lane accounting now goes through
    BatchVerifier._stats_lock (enforced by the tools/analyze
    lock-discipline pass), so N concurrent single-item dispatches into
    bucket size B must count EXACTLY N*(B-1) padded lanes."""
    import threading

    eng = BatchVerifier(max_batch=8, buckets=(8,))
    # Materialize the queue (dispatchers update its stats slot directly)
    # and warm the kernel so the threads race on accounting, not compile.
    eng._queue("hmac_sha256", eng._dispatch_hmac)
    eng._dispatch_hmac([_hmac_item(0)])
    base = eng.stats["hmac_sha256"].padded_lanes
    n_threads, per_thread = 8, 4
    barrier = threading.Barrier(n_threads)

    def hammer(tid: int) -> None:
        barrier.wait()
        for j in range(per_thread):
            res = eng._dispatch_hmac([_hmac_item(100 + tid * per_thread + j)])
            assert bool(res[0])

    threads = [
        threading.Thread(target=hammer, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    got = eng.stats["hmac_sha256"].padded_lanes - base
    assert got == n_threads * per_thread * 7  # bucket 8, batch 1 -> 7 pads


# ---------------------------------------------------------------------------
# the dispatch record (obs/trace.py DISPATCH_COLUMNS): one row per counted batch


def _rows(eng):
    from minbft_tpu.obs.trace import DISPATCH_COLUMNS

    return [dict(zip(DISPATCH_COLUMNS, e)) for e in eng.drain_obs_events()]


def _instants(row):
    from minbft_tpu.obs.trace import DISPATCH_COLUMNS

    return [row[c] for c in DISPATCH_COLUMNS[8:]]


def test_dispatch_rows_written_from_max_inflight_workers_are_never_torn():
    """max_inflight dispatchers stamp their instants on worker threads at
    once; every row still belongs to one dispatch: its own id, its own
    items and lanes, eight instants that never decrease, and the rows
    number exactly the counted batches."""

    async def run():
        eng = BatchVerifier(max_batch=2, buckets=(2,), max_inflight=4)
        items = [_hmac_item(i) for i in range(61)]
        oks = await asyncio.gather(*[eng.verify_hmac_sha256(*it) for it in items])
        assert all(oks)
        st = eng.stats["hmac_sha256"]
        rows = _rows(eng)
        assert len(rows) == st.batches >= 31
        assert sum(r["items"] for r in rows) == st.items == 61
        assert len({r["dispatch_id"] for r in rows}) == len(rows)
        for r in rows:
            assert r["lanes"] == 2 and 1 <= r["items"] <= 2 and r["flags"] == 0
            t = _instants(r)
            assert t == sorted(t) and t[0] > 0
        # dispatches overlapped on their worker threads
        spans = sorted((r["t_worker_start"], r["t_result"]) for r in rows)
        assert any(b[0] < a[1] for a, b in zip(spans, spans[1:]))
        reasons = {}
        for r in rows:
            reasons[r["reason"]] = reasons.get(r["reason"], 0) + 1
        assert reasons == st.flush_reasons

    asyncio.run(run())


def test_dispatch_rows_flag_timeout_fallback_and_host_queues():
    """Fallback and timed-out dispatches are recorded with a flag, not
    dropped; a host queue's rows say that no device phase ran; a dispatch
    that raised is counted in neither batches nor rows."""
    import threading

    import numpy as np

    from minbft_tpu.obs import trace as obs_trace

    async def run():
        eng = BatchVerifier(max_batch=8, dispatch_timeout=0.2)
        hang = threading.Event()

        def hanging_dispatch(items):
            hang.wait(30)

        eng._host_fallback_for = lambda name: (
            lambda items: np.array([True] * len(items), dtype=bool)
        )
        q = eng._queue("ecdsa_p256", hanging_dispatch)
        for k in range(4):  # three hangs write the device off; the fourth skips it
            assert await asyncio.wait_for(q.submit(b"item-%d" % k), 10) is True
        hang.set()
        rows = _rows(eng)
        assert len(rows) == q.stats.batches == 4
        timed_out = obs_trace.FLAG_FALLBACK | obs_trace.FLAG_TIMEOUT | obs_trace.FLAG_NO_DEVICE
        assert [r["flags"] for r in rows[:3]] == [timed_out] * 3
        assert rows[3]["flags"] == obs_trace.FLAG_FALLBACK | obs_trace.FLAG_NO_DEVICE
        for r in rows:
            t = _instants(r)
            assert t == sorted(t)
        # the timeout shows in the row: flush to resolved spans it
        assert rows[0]["t_resolved"] - rows[0]["t_flush"] >= 0.2e9 * 4  # first dispatch: 4x
        assert rows[3]["t_resolved"] - rows[3]["t_flush"] < 0.15e9

        host = BatchVerifier(max_batch=8)
        from minbft_tpu.utils import hostcrypto as hc

        d, pub = hc.keygen()
        dg = hashlib.sha256(b"host").digest()
        assert await host.verify_ecdsa_p256_host(pub, dg, hc.ecdsa_sign(d, dg))
        (row,) = _rows(host)
        assert row["queue"] == "ecdsa_p256_host"
        assert row["flags"] == obs_trace.FLAG_NO_DEVICE and row["lanes"] == 0

        def raising(items):
            raise RuntimeError("dispatch failed")

        bad = BatchVerifier(max_batch=8, dispatch_timeout=0)
        try:
            await bad._queue("hmac_sha256", raising).submit(b"x")
        except RuntimeError:
            pass
        assert bad.stats["hmac_sha256"].batches == 0 and _rows(bad) == []

    asyncio.run(run())


def test_the_dispatch_row_is_never_in_the_callers_way():
    """The batch's futures resolve before its row is written, so a fault
    in the recorder cannot hang the protocol; a flush reason the table
    lacks reads `other`; and the row is made from a copy of the span's
    instants, so a timed-out worker stamping late changes no written row."""
    from minbft_tpu.obs import trace as obs_trace

    async def run():
        eng = BatchVerifier(max_batch=8, buckets=(8,))
        q = eng._queue("hmac_sha256", eng._dispatch_hmac)
        noted = []

        def broken_note(span, batch, *rest):
            noted.append([f.done() for _it, f, _t in batch])
            raise RuntimeError("recorder fault")

        q._note_dispatch = broken_note
        assert await asyncio.wait_for(eng.verify_hmac_sha256(*_hmac_item(1)), 30)
        assert noted == [[True]]  # resolved first
        del q._note_dispatch

        fut = asyncio.get_running_loop().create_future()
        item = _hmac_item(2)
        q.inflight += 1
        q._inflight_futs[item] = [fut]
        await q._run([(item, fut, obs_trace.time.monotonic_ns())], "a-new-reason")
        assert fut.result() is True
        (row,) = _rows(eng)
        assert row["reason"] == "other"
        assert q.stats.flush_reasons["a-new-reason"] == 1

        span = engine_mod._DispatchSpan(engine_mod._phase_names("x"), 99)
        span.t[:] = [5, 3, 9, 0, 0]  # out of order, as a racing worker could leave them
        q._note_dispatch(span, [(None, None, 1)], "idle", 4, 20, False)
        assert span.t == [5, 3, 9, 0, 0]  # the span itself is not rewritten
        assert _instants(_rows(eng)[-1]) == [1, 4, 5, 5, 9, 9, 9, 20]

    from minbft_tpu.parallel import engine as engine_mod

    asyncio.run(run())


def test_dispatch_annotations_do_not_raise_without_a_profiler_session():
    """The worker's phases and the loop's resolve are TraceAnnotations:
    with no session they are a flag test, and the probe of a written-off
    device (a task whose context was copied from a live dispatch) stamps
    into no live row."""
    from minbft_tpu.parallel import engine as engine_mod

    span = engine_mod._DispatchSpan(engine_mod._phase_names("ecdsa_p256"), 7)
    with span.phase("prep", span.PREP):
        pass
    assert span.t[span.PREP] > 0 and span.t[span.LAUNCH] == 0
    assert engine_mod._SPAN.get() is None
    direct = engine_mod._worker_span()  # a dispatcher called outside _run
    assert direct.dispatch_id == 0 and direct.t[0] > 0

    async def run():
        eng = BatchVerifier(max_batch=8, buckets=(8,))
        assert await eng.verify_hmac_sha256(*_hmac_item(1))
        (row,) = _rows(eng)
        assert row["t_launch_end"] > row["t_prep_end"] > row["t_worker_start"]
        assert engine_mod._SPAN.get() is None  # set in _run's own context only

    asyncio.run(run())
