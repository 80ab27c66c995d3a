"""Flight-recorder subsystem tests (minbft_tpu/obs, ISSUE 4): ring
semantics under concurrency, histogram correctness against the reservoir
oracle, recorder pairing, and the dump→ingest stage table."""

import asyncio
import json
import os
import random
import threading

import pytest

from minbft_tpu.obs.hist import Log2Histogram
from minbft_tpu.obs.trace import (
    CLIENT_STAGES,
    REPLICA_STAGES,
    FlightRecorder,
    MTStageRing,
    StageRing,
    dump_recorder,
    load_dumps,
    stage_table,
)
from minbft_tpu.utils.metrics import LatencyReservoir


# ---------------------------------------------------------------------------
# rings


def test_stage_ring_orders_and_wraps():
    r = StageRing(capacity=8)
    assert r.capacity == 8
    for k in range(5):
        r.push(1, k, 2, 100 + k)
    assert len(r) == 5
    assert [e[1] for e in r.snapshot()] == [0, 1, 2, 3, 4]
    for k in range(5, 20):
        r.push(1, k, 2, 100 + k)
    # wrapped: only the newest `capacity` events remain, still in order
    assert len(r) == 8
    assert [e[1] for e in r.snapshot()] == list(range(12, 20))
    assert [e[1] for e in r.snapshot(limit=3)] == [17, 18, 19]


def test_stage_ring_capacity_rounds_to_power_of_two():
    assert StageRing(capacity=100).capacity == 128
    assert MTStageRing(capacity=100).capacity == 128


def test_mt_ring_multi_producer_hammer():
    """Engine-worker-shaped hammer: several OS threads push concurrently;
    every surviving row must be internally consistent (a torn row — one
    thread's column interleaved into another's — would break the a+b==c
    invariant each producer maintains)."""
    ring = MTStageRing(capacity=1024)
    n_threads, per_thread = 8, 3000

    def producer(tid: int) -> None:
        for k in range(per_thread):
            ring.push(tid, k, tid + k, tid * 1_000_000 + k)

    threads = [
        threading.Thread(target=producer, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = ring.snapshot()
    assert len(snap) == 1024  # saturated
    per_tid_last = {}
    for a, b, c, t in snap:
        assert 0 <= a < n_threads
        assert c == a + b, "torn row: columns from different producers"
        assert t == a * 1_000_000 + b
        # per-producer order is preserved (the lock serializes pushes)
        assert per_tid_last.get(a, -1) < b
        per_tid_last[a] = b


def test_mt_ring_event_loop_plus_worker_threads():
    """The deployment shape: the event loop and asyncio.to_thread
    workers (engine dispatcher stand-ins) produce into one ring while
    the loop also drains snapshots mid-flight."""

    async def run():
        ring = MTStageRing(capacity=4096)

        def worker(tid: int) -> None:
            for k in range(500):
                ring.push(tid, k, tid + k, k)

        async def loop_producer() -> None:
            for k in range(500):
                ring.push(99, k, 99 + k, k)
                if k % 50 == 0:
                    for a, b, c, _ in ring.snapshot(limit=64):
                        assert c == a + b
                    await asyncio.sleep(0)

        await asyncio.gather(
            loop_producer(),
            *[asyncio.to_thread(worker, t) for t in range(4)],
        )
        snap = ring.snapshot()
        assert len(snap) == 4 * 500 + 500  # nothing lost below capacity
        for a, b, c, _ in snap:
            assert c == a + b

    asyncio.run(run())


def test_engine_ring_records_one_dispatch_row_per_batch():
    """The engine's always-on dispatch record: one row per counted batch,
    written by _run on the loop with the instants the dispatcher stamped
    on its worker thread; drain decodes queue, kind and flush reason."""
    from minbft_tpu.obs import trace as obs_trace
    from minbft_tpu.parallel import BatchVerifier

    async def run():
        eng = BatchVerifier(max_batch=8, buckets=(8,))
        key, msg, mac = b"\x11" * 32, b"\x22" * 32, b"\x33" * 32
        import hashlib
        import hmac as hmac_mod

        good = hmac_mod.new(key, msg, hashlib.sha256).digest()
        oks = await asyncio.gather(
            *[eng.verify_hmac_sha256(key, msg, good) for _ in range(8)]
        )
        assert all(oks)
        events = eng.drain_obs_events()
        assert len(events) == eng.stats["hmac_sha256"].batches > 0
        cols = obs_trace.DISPATCH_COLUMNS
        for e in events:
            row = dict(zip(cols, e))
            assert row["engine"] == eng.obs_id
            assert (row["queue"], row["kind"]) == ("hmac_sha256", "verify")
            assert row["reason"] in obs_trace.FLUSH_REASONS
            assert row["lanes"] == 8 and 0 < row["items"] <= 8
            assert row["flags"] == 0
            instants = e[cols.index("t_first_enqueue"):]
            assert len(instants) == 8 and instants[0] > 0
            assert list(instants) == sorted(instants)
            # the device phases took time; verify has no finish phase
            assert row["t_result"] > row["t_worker_start"]
            assert row["t_finish_end"] == row["t_result"]
        # the ring always exists: an engine that has dispatched nothing
        # reads empty, and the two engines' ids differ
        eng2 = BatchVerifier(max_batch=8, buckets=(8,))
        assert eng2.drain_obs_events() == [] and eng2.obs_id != eng.obs_id
        # ... and both are on the process timeline, by id, while alive
        by_id = {d["engine"]: d for d in obs_trace.timeline()["dispatch"]}
        assert by_id[eng.obs_id]["rows"] == events
        assert by_id[eng.obs_id]["dropped"] == 0
        assert by_id[eng2.obs_id]["rows"] == []

    asyncio.run(run())


def test_ring_rows_widen_and_dropped_counts_a_wrapped_ring():
    """A ring's row is as wide as it was made; `dropped` counts the rows
    a wrapped ring has overwritten, and `read` gives both together."""
    for cls in (StageRing, MTStageRing):
        r = cls(capacity=4, width=6)
        assert r.read() == ([], 0)
        for k in range(3):
            r.push_row((k, 1, 2, 3, 4, 5))
        assert r.read() == ([(k, 1, 2, 3, 4, 5) for k in range(3)], 0)
        for k in range(3, 11):
            r.push_row((k, 1, 2, 3, 4, 5))
        rows, dropped = r.read()
        assert [row[0] for row in rows] == [7, 8, 9, 10]
        assert dropped == 7
        with pytest.raises(ValueError):
            r.push_row((1, 2, 3, 4))  # a row of another width is refused, not cut
        with pytest.raises(ValueError):
            r.push(1, 2, 3, 4)  # the four-store push is the four-wide ring's
        assert r.read() == (rows, 7)
        # a four-wide ring takes both, and they land in the same rows
        four = cls(capacity=4)
        four.push(1, 2, 3, 4)
        four.push_row((5, 6, 7, 8))
        assert four.read() == ([(1, 2, 3, 4), (5, 6, 7, 8)], 0)


def test_collector_ring_catches_a_forced_full_pass():
    import gc

    from minbft_tpu.obs import trace as obs_trace

    obs_trace.install_collector_clock()
    obs_trace.install_collector_clock()  # once per process, however often asked
    assert gc.callbacks.count(obs_trace._on_gc) == 1
    before = len(obs_trace.timeline()["gc"]["rows"])
    t0 = obs_trace.time.monotonic_ns()
    gc.collect(2)
    t1 = obs_trace.time.monotonic_ns()
    rows = obs_trace.timeline()["gc"]["rows"]
    assert len(rows) == before + 1
    generation, t_start, duration_ns = rows[-1]
    assert generation == 2 and t0 <= t_start and t_start + duration_ns <= t1
    # short passes of the young generations are not recorded
    gc.collect(0)
    newest = obs_trace.timeline()["gc"]["rows"][-1]
    assert newest == rows[-1] or newest[2] >= obs_trace._MIN_NS


@pytest.mark.parametrize("how,low,high", [("sleeps", 0.5, 1.01), ("spins", 0.0, 0.5)])
def test_loop_idle_clock_tells_a_sleeping_loop_from_a_spinning_one(how, low, high):
    """The idle clock reads about 1 on a loop that sleeps and about 0 on
    one that spins (coarse: nothing here is tighter than 2x)."""
    import time

    from minbft_tpu.obs import looplag

    async def run():
        loop = asyncio.get_running_loop()
        clock = looplag.install_idle_clock(loop)
        assert clock is not None
        assert looplag.install_idle_clock(loop) is clock  # once per loop
        t0 = time.monotonic_ns()
        if how == "sleeps":
            await asyncio.sleep(0.3)
        else:
            while time.monotonic_ns() - t0 < 300_000_000:
                await asyncio.sleep(0)  # a turn of the loop, always runnable
        t1 = time.monotonic_ns()
        mine = [c for c in looplag.idle_clocks() if c["current"]]
        assert len(mine) == 1
        rec = mine[0]
        assert rec["slot_ns"] == 10_000_000 and rec["from_ns"] <= t0
        lo, hi = t0 // rec["slot_ns"], t1 // rec["slot_ns"]
        idle = sum(ns for s, ns in rec["idle"] if lo < s < hi)
        assert all(0 < ns <= rec["slot_ns"] for _s, ns in rec["idle"])
        return idle / ((hi - lo - 1) * rec["slot_ns"])

    share = asyncio.run(run())
    assert low <= share <= high, share


def test_loop_idle_clock_records_nothing_on_a_loop_without_a_selector():
    from minbft_tpu.obs import looplag

    class NoSelector:
        pass

    assert looplag.install_idle_clock(NoSelector()) is None


def test_loop_idle_clock_splits_a_sleep_over_its_slots():
    from minbft_tpu.obs.looplag import LoopIdleClock

    clock = LoopIdleClock()
    ns = clock.SLOT_NS
    base = (clock.since_ns // ns + 2) * ns
    clock.add(base + 4_000_000, base + 2 * ns + 1_000_000)
    clock.add(base + 2 * ns + 5_000_000, base + 2 * ns + 6_000_000)
    got = {s - base // ns: idle for s, idle in clock.read()["idle"]}
    assert got == {0: 6_000_000, 1: ns, 2: 2_000_000}


def test_client_rows_count_one_per_request():
    """Always on, no recorder: one `start` row a request on the process
    timeline (what finds the benchmark's window), and nothing else."""
    from minbft_tpu.client import new_client
    from minbft_tpu.obs import trace as obs_trace
    from minbft_tpu.sample.authentication import generate_testnet_keys
    from minbft_tpu.sample.config import SimpleConfiger
    from minbft_tpu.sample.conn.inprocess import InProcessClientConnector
    from minbft_tpu.sample.peer.placement import start_local_cluster

    async def run():
        store = generate_testnet_keys(3, n_clients=1)
        cfg = SimpleConfiger(n=3, f=1, timeout_request=30.0, timeout_prepare=15.0)
        cluster = await start_local_cluster(store, cfg, no_batch=True)
        client = new_client(
            0, 3, 1, store.client_authenticator(0),
            InProcessClientConnector(cluster.stubs),
        )
        return cluster, client

    async def drive():
        cluster, client = await run()
        try:
            await client.start()
            assert client._trace is None  # the gated recorder is off
            mark = len(obs_trace.timeline()["client"]["rows"])
            for k in range(5):
                await asyncio.wait_for(client.request(b"op-%d" % k), 30)
            rows = obs_trace.timeline()["client"]["rows"][mark:]
        finally:
            await client.stop()
            await cluster.stop()
        return rows

    rows = asyncio.run(drive())
    mine = [r for r in rows if r[0] == 0]
    assert [r[2] for r in mine] == ["start"] * 5
    assert len({seq for _cid, seq, _stage, _t in mine}) == 5
    assert [r[3] for r in mine] == sorted(r[3] for r in mine)


def test_engine_queue_wait_and_service_histograms():
    """Queue-wait attribution (ISSUE 8): every successfully dispatched
    item records one enqueue→dispatch wait and one dispatch→complete
    service span — count == items — and both surface in the Prometheus
    exposition."""
    import hashlib
    import hmac as hmac_mod

    from minbft_tpu.obs.prom import collect_replica, render_families
    from minbft_tpu.parallel import BatchVerifier

    async def run():
        eng = BatchVerifier(max_batch=4, buckets=(4,))
        key, msg = b"\x01" * 32, b"\x02" * 32
        good = hmac_mod.new(key, msg, hashlib.sha256).digest()
        items = [(key, msg, good[:-1] + bytes([i])) for i in range(9)]
        await asyncio.gather(*[eng.verify_hmac_sha256(*it) for it in items])
        st = eng.stats["hmac_sha256"]
        assert st.queue_wait.count == st.items == 9
        assert st.queue_service.count == st.items
        assert st.queue_wait.negatives == 0
        assert st.queue_service.total_s > 0
        # sign side mirrors it (host fallback on the CPU backend still
        # flows through the queue — the spans are queue properties)
        from minbft_tpu.utils import hostcrypto as hc

        d, _ = hc.keygen()
        await eng.sign_ecdsa_p256(d, hashlib.sha256(b"qw").digest())
        sst = eng.sign_stats["ecdsa_p256"]
        assert sst.queue_wait.count == sst.items == 1
        assert sst.queue_service.count == 1
        text = render_families(collect_replica(engine=eng))
        assert "minbft_verify_queue_wait_seconds_bucket" in text
        assert "minbft_verify_queue_service_seconds_count" in text
        assert "minbft_sign_queue_wait_seconds_bucket" in text

    asyncio.run(run())


def test_loop_lag_sampler_records_blocking(monkeypatch):
    """The event-loop lag sampler sees a deliberate loop block: the max
    observed lag must be at least the blocked interval (minus one tick),
    and stop() tears the task down."""
    import time as time_mod

    from minbft_tpu.obs.looplag import LoopLagSampler, maybe_sampler

    async def run():
        hist = Log2Histogram()
        sampler = LoopLagSampler(hist, interval=0.01)
        sampler.start()
        await asyncio.sleep(0.05)  # healthy ticks
        time_mod.sleep(0.08)  # block the loop (the GIL-saturation shape)
        await asyncio.sleep(0.03)
        sampler.stop()
        await asyncio.sleep(0)  # let the cancellation land
        assert hist.count >= 3
        assert hist.negatives == 0
        # one sample must carry the ~80ms block: p100 >= 32ms bucket
        assert hist.percentile(100) >= 0.032
        # and most ticks are healthy: p50 well under the block
        assert hist.percentile(50) < 0.032

    asyncio.run(run())
    # env knob: 0 disables, garbage falls back to the default
    monkeypatch.setenv("MINBFT_LOOPLAG_INTERVAL", "0")
    assert maybe_sampler(Log2Histogram()) is None
    monkeypatch.setenv("MINBFT_LOOPLAG_INTERVAL", "not-a-number")
    assert maybe_sampler(Log2Histogram()) is not None
    monkeypatch.delenv("MINBFT_LOOPLAG_INTERVAL")
    s = maybe_sampler(Log2Histogram())
    assert s is not None and s.interval == 0.05


def test_replica_dump_carries_loop_lag_and_nf(tmp_path, monkeypatch):
    """A replica's shutdown dump carries n/f and the sampled loop-lag
    histogram — the critpath merge's quorum rank and loop_lag inputs."""
    from conftest import make_cluster
    from minbft_tpu.obs import trace as trace_mod
    from minbft_tpu.sample.config import SimpleConfiger

    async def run():
        cfg = SimpleConfiger(
            n=4, f=1, timeout_request=60.0, timeout_prepare=30.0
        )
        cfg.trace = True
        replicas, _c_auths, _stubs, _ledgers = await make_cluster(4, 1, cfg=cfg)
        await asyncio.sleep(0.12)  # let the lag samplers tick
        monkeypatch.setenv(
            trace_mod.TRACE_DUMP_ENV, str(tmp_path / "dump")
        )
        for r in replicas:
            await r.stop()

    asyncio.run(run())
    docs = load_dumps(str(tmp_path / "dump"))
    assert len(docs) == 4
    for doc in docs:
        assert doc["n"] == 4 and doc["f"] == 1
        assert doc["clock_domain"]
        lag = Log2Histogram.from_dict(doc["loop_lag"])
        assert lag.count > 0


def test_trace_dump_fires_on_fatal_task_crash(tmp_path, monkeypatch):
    """A replica task dying with an exception dumps the trace at the
    moment of death — a crashed soak must not lose its forensics (the
    dump used to fire only on clean stop)."""
    from conftest import make_cluster
    from minbft_tpu.obs import trace as trace_mod
    from minbft_tpu.sample.config import SimpleConfiger

    monkeypatch.setenv(trace_mod.TRACE_DUMP_ENV, str(tmp_path / "crash"))

    async def run():
        cfg = SimpleConfiger(
            n=4, f=1, timeout_request=60.0, timeout_prepare=30.0
        )
        cfg.trace = True
        replicas, _c, _stubs, _ledgers = await make_cluster(4, 1, cfg=cfg)
        try:
            replicas[0].handlers.trace.note(1, 9, 9)  # something to dump
            # Kill one protocol task the way a real bug would: make it
            # raise, then let the done-callback observe the corpse.
            victim = replicas[0]._tasks[0]
            victim.cancel()  # unwind it...
            await asyncio.sleep(0)

            async def boom():
                raise RuntimeError("injected fatal task error")

            t = asyncio.get_running_loop().create_task(boom())
            t.add_done_callback(replicas[0]._on_task_done)
            await asyncio.sleep(0.05)
            assert os.path.exists(str(tmp_path / "crash") + ".r0.json")
        finally:
            for r in replicas:
                await r.stop()

    asyncio.run(run())
    docs = load_dumps(str(tmp_path / "crash"))
    assert any(d["kind"] == "replica" and d["id"] == 0 for d in docs)


def test_engine_flush_reasons_and_occupancy_sum_to_batches():
    from minbft_tpu.parallel import BatchVerifier

    async def run():
        eng = BatchVerifier(max_batch=4, buckets=(4,))
        import hashlib
        import hmac as hmac_mod

        key, msg = b"\x01" * 32, b"\x02" * 32
        good = hmac_mod.new(key, msg, hashlib.sha256).digest()
        # distinct MACs so nothing dedups away
        items = [
            (key, msg, good[:-1] + bytes([i])) for i in range(16)
        ] + [(key, msg, good)]
        await asyncio.gather(
            *[eng.verify_hmac_sha256(*it) for it in items]
        )
        st = eng.stats["hmac_sha256"]
        assert st.batches >= 1
        assert sum(st.flush_reasons.values()) == st.batches
        assert sum(st.occupancy.values()) == st.batches
        assert set(st.flush_reasons) <= {
            "full", "idle", "timer", "completion", "direct"
        }
        assert eng.queue_depths()["hmac_sha256"] == 0  # drained
        assert eng.sign_queue_depths() == {}

    asyncio.run(run())


# ---------------------------------------------------------------------------
# histograms


def test_log2_histogram_bucket_edges():
    h = Log2Histogram()
    h.observe(0.5e-6)   # <= 1us -> bucket 0
    h.observe(1e-6)     # == 1us -> bucket 0
    h.observe(2e-6)     # bucket 1
    h.observe(3e-6)     # bucket 2 (2 < 3 <= 4)
    assert h.buckets[0] == 2 and h.buckets[1] == 1 and h.buckets[2] == 1
    assert h.count == 4


def test_log2_histogram_counts_negative_durations():
    """Clock weirdness is COUNTED, never silently clamped (ISSUE 8): a
    negative duration lands in ``negatives`` only — buckets, count, and
    total stay unpolluted — and the counter rides merge, the dump round
    trip, and the Prometheus exposition."""
    h = Log2Histogram()
    h.observe(1e-6)
    h.observe(-1.0)
    h.observe_ns(-5)
    assert h.negatives == 2
    assert h.count == 1 and h.buckets[0] == 1
    assert h.total_s == pytest.approx(1e-6)

    other = Log2Histogram()
    other.observe(-2.0)
    h.merge(other)
    assert h.negatives == 3

    d = json.loads(json.dumps(h.to_dict()))
    assert Log2Histogram.from_dict(d).negatives == 3
    clean = Log2Histogram()
    clean.observe(1e-3)
    assert "negatives" not in clean.to_dict()  # sparse: only when nonzero

    from minbft_tpu.obs.prom import render_families

    text = render_families(
        [("lat_seconds", "histogram", "x", [({"stage": "s"}, h)])]
    )
    assert 'lat_seconds_negatives_total{stage="s"} 3' in text
    assert "# TYPE lat_seconds_negatives_total counter" in text
    clean_text = render_families(
        [("lat_seconds", "histogram", "x", [({"stage": "s"}, clean)])]
    )
    assert "negatives" not in clean_text


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_histogram_percentile_vs_reservoir_oracle(seed):
    """Property: on identical samples the histogram's percentile is the
    nearest-rank value rounded UP to its bucket edge — within a factor
    of 2 above the reservoir oracle's exact answer, never below it."""
    rng = random.Random(seed)
    hist = Log2Histogram()
    oracle = LatencyReservoir(capacity=10_000)  # holds every sample
    samples = []
    for _ in range(3000):
        # log-uniform over ~1us..10s — the range of real stage spans
        v = 10 ** rng.uniform(-6, 1)
        samples.append(v)
        hist.observe(v)
        oracle.observe(v)
    assert hist.count == oracle.count == 3000
    assert abs(hist.total_s - sum(samples)) < 1e-6 * hist.count
    for q in (1, 25, 50, 90, 99):
        exact = oracle.percentile(q)
        approx = hist.percentile(q)
        assert exact * (1 - 1e-9) <= approx <= exact * 2 + 2e-6, (
            q, exact, approx,
        )


def test_histogram_merge_equals_concatenation():
    rng = random.Random(7)
    a, b, both = Log2Histogram(), Log2Histogram(), Log2Histogram()
    for i in range(2000):
        v = 10 ** rng.uniform(-6, 0)
        (a if i % 2 else b).observe(v)
        both.observe(v)
    merged = Log2Histogram.merged([a, b])
    assert merged.buckets == both.buckets
    assert merged.count == both.count
    assert abs(merged.total_s - both.total_s) < 1e-9
    for q in (50, 99):
        assert merged.percentile(q) == both.percentile(q)


def test_histogram_dict_round_trip():
    h = Log2Histogram()
    for v in (1e-6, 5e-4, 0.25, 3.0):
        h.observe(v)
    d = json.loads(json.dumps(h.to_dict()))  # survives JSON
    h2 = Log2Histogram.from_dict(d)
    assert h2.buckets == h.buckets and h2.count == h.count
    assert abs(h2.total_s - h.total_s) < 1e-12


# ---------------------------------------------------------------------------
# recorder pairing + stage table


def test_recorder_pairs_consecutive_points_and_retires_keys():
    rec = FlightRecorder.for_replica(0)
    assert rec.stages == REPLICA_STAGES
    for stage in range(len(REPLICA_STAGES)):
        rec.note(stage, 5, 42)
    hists = rec.stage_hists()
    # BOTH replica entry stages (ingest and recv) open spans without
    # closing one, so N points yield N-2 spans
    assert set(hists) == set(REPLICA_STAGES[2:])
    assert all(h.count == 1 for h in hists.values())
    assert rec._last == {}, "final stage must retire the pairing key"
    assert len(rec.ring) == len(REPLICA_STAGES)


def test_recorder_inflight_keys_are_bounded():
    from minbft_tpu.obs import trace as trace_mod

    rec = FlightRecorder.for_replica(0)
    cap = trace_mod._MAX_INFLIGHT_KEYS
    for k in range(cap + 10):  # never-completing requests
        rec.note(0, 0, k)
    assert len(rec._last) <= cap


def test_stage_table_from_dumped_recorders(tmp_path):
    base = str(tmp_path / "trace")
    for rid in (0, 1):
        rec = FlightRecorder.for_replica(rid)
        for seq in range(10):
            for stage in range(len(REPLICA_STAGES)):
                rec.note(stage, 1, seq)
        assert dump_recorder(rec, base=base) is not None
    crec = FlightRecorder.for_client(1)
    for seq in range(10):
        for stage in range(len(CLIENT_STAGES)):
            crec.note(stage, 1, seq)
    dump_recorder(crec, base=base)

    docs = load_dumps(base)
    assert len(docs) == 3
    table = stage_table(docs, "t")
    # entry stages (ingest, recv) never record spans — no table keys
    for name in REPLICA_STAGES[2:]:
        assert f"t_stage_{name}_p50_ms" in table
        assert f"t_stage_{name}_share" in table
    for name in CLIENT_STAGES[1:]:
        assert f"t_stage_client_{name}_p50_ms" in table
        # client spans overlap the replica pipeline: no share key
        assert f"t_stage_client_{name}_share" not in table
    shares = [v for k, v in table.items() if k.endswith("_share")]
    assert abs(sum(shares) - 1.0) < 0.01

    # empty dumps (tracing off) produce NO keys — the bench's
    # byte-identical-keys contract
    assert stage_table([], "t") == {}
    assert stage_table([{"kind": "replica", "hists": {}}], "t") == {}


def test_tracing_enabled_env_parsing(monkeypatch):
    """MINBFT_TRACE follows the repo's env-flag convention: the usual
    falsy spellings DISABLE; MINBFT_TRACE_DUMP is a path (any non-empty
    value enables)."""
    from minbft_tpu.obs.trace import tracing_enabled

    monkeypatch.delenv("MINBFT_TRACE", raising=False)
    monkeypatch.delenv("MINBFT_TRACE_DUMP", raising=False)
    assert not tracing_enabled()
    for off in ("0", "false", "no", ""):
        monkeypatch.setenv("MINBFT_TRACE", off)
        assert not tracing_enabled(), off
    monkeypatch.setenv("MINBFT_TRACE", "1")
    assert tracing_enabled()
    monkeypatch.setenv("MINBFT_TRACE", "0")
    monkeypatch.setenv("MINBFT_TRACE_DUMP", "/tmp/somewhere")
    assert tracing_enabled()


def test_flush_reasons_skip_failed_dispatches():
    """The 'flush_reasons and occupancy both sum to batches' invariant
    must hold on error paths: a batch whose dispatch raises is counted
    in none of the three."""
    import asyncio as aio

    from minbft_tpu.parallel import BatchVerifier
    from minbft_tpu.parallel.engine import _SchemeQueue

    async def run():
        eng = BatchVerifier(max_batch=4, buckets=(4,), dispatch_timeout=0)

        def boom(items):
            raise RuntimeError("dispatch exploded")

        q = _SchemeQueue(eng, "boom", boom)
        eng._queues["boom"] = q
        with pytest.raises(RuntimeError):
            await q.submit((b"x",))
        assert q.stats.batches == 0
        assert sum(q.stats.flush_reasons.values()) == 0
        assert sum(q.stats.occupancy.values()) == 0

    aio.run(run())


def test_dump_respects_env_and_noop_when_unset(tmp_path, monkeypatch):
    from minbft_tpu.obs import trace as trace_mod

    rec = FlightRecorder.for_replica(3)
    rec.note(0, 1, 1)
    monkeypatch.delenv(trace_mod.TRACE_DUMP_ENV, raising=False)
    assert dump_recorder(rec) is None  # env unset, explicit base absent
    monkeypatch.setenv(trace_mod.TRACE_DUMP_ENV, str(tmp_path / "envtrace"))
    path = dump_recorder(rec)
    assert path is not None and path.endswith(".r3.json")
    assert os.path.exists(path)
    doc = load_dumps(str(tmp_path / "envtrace"))[0]
    assert doc["kind"] == "replica" and doc["id"] == 3
    assert doc["events"], "ring events must land in the dump"
