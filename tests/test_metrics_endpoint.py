"""Prometheus exposition tests: text-format rendering, the stdlib
``/metrics`` endpoint scraped off a live in-process cluster, and the
``peer metrics`` one-shot subcommand."""

import asyncio
import os
import sys
import urllib.request

import pytest

from minbft_tpu.obs.hist import Log2Histogram
from minbft_tpu.obs.prom import (
    CONTENT_TYPE,
    MetricsServer,
    collect_replica,
    render_families,
    scrape,
)

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from conftest import make_cluster  # noqa: E402


# ---------------------------------------------------------------------------
# rendering


def test_render_counters_and_gauges():
    text = render_families(
        [
            ("m_total", "counter", "help text", [({"replica": "0"}, 3)]),
            ("g", "gauge", "a gauge", [({}, 1.5)]),
            ("empty", "counter", "skipped entirely", []),
        ]
    )
    assert "# HELP m_total help text" in text
    assert "# TYPE m_total counter" in text
    assert 'm_total{replica="0"} 3' in text
    assert "g 1.5" in text
    assert "empty" not in text


def test_render_histogram_is_cumulative_with_inf():
    h = Log2Histogram()
    for v in (1e-6, 1e-6, 3e-6, 1e-3):
        h.observe(v)
    text = render_families(
        [("lat_seconds", "histogram", "latency", [({"stage": "s"}, h)])]
    )
    lines = [ln for ln in text.splitlines() if ln.startswith("lat_seconds")]
    buckets = [ln for ln in lines if "_bucket" in ln]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert counts[-1] == 4
    assert 'le="+Inf"' in buckets[-1]
    assert 'lat_seconds_count{stage="s"} 4' in text
    assert any(ln.startswith("lat_seconds_sum") for ln in lines)


def test_collect_replica_families_from_live_objects():
    from minbft_tpu.obs.trace import FlightRecorder
    from minbft_tpu.utils.metrics import ReplicaMetrics

    m = ReplicaMetrics()
    m.inc("requests_executed", 2)
    m.observe_execute(0.01)
    from minbft_tpu.obs.trace import R_INGEST, R_VERIFY_ENQUEUE

    rec = FlightRecorder.for_replica(1)
    rec.note(R_INGEST, 0, 1)
    rec.note(R_VERIFY_ENQUEUE, 0, 1)
    text = render_families(collect_replica(metrics=m, recorder=rec, replica_id=1))
    assert 'minbft_requests_executed_total{replica="1"} 2' in text
    assert "minbft_uptime_seconds" in text
    assert "minbft_execute_latency_seconds_count" in text
    assert 'minbft_stage_latency_seconds_count{replica="1",stage="verify_enqueue"} 1' in text


# ---------------------------------------------------------------------------
# live endpoint


def test_metrics_endpoint_scrapes_a_committing_cluster():
    """Acceptance smoke: a 4-replica in-process cluster commits requests
    with the flight recorder on; the stdlib endpoint serves Prometheus
    text that carries the protocol counters, the stage histograms, AND
    the engine queue gauges — scraped over real HTTP while the loop is
    live, by raw urllib and by the `peer metrics` subcommand."""

    async def run():
        from minbft_tpu.client import new_client
        from minbft_tpu.parallel import BatchVerifier
        from minbft_tpu.sample.config import SimpleConfiger
        from minbft_tpu.sample.conn.inprocess import InProcessClientConnector

        cfg = SimpleConfiger(
            n=4, f=1, timeout_request=60.0, timeout_prepare=30.0
        )
        cfg.trace = True  # flight recorder on for every replica
        engine = BatchVerifier(max_batch=8, buckets=(8,))
        # batch_signatures=False: message signatures stay on the host
        # queue, so the only device kernel this test compiles is the
        # cheap HMAC USIG one (the CPU-backend ECDSA verify kernel takes
        # minutes to build — not a price a smoke test pays).
        replicas, c_auths, stubs, _ledgers = await make_cluster(
            4, 1, cfg=cfg, engines=[engine] * 4, batch_signatures=False
        )
        client = new_client(
            0, 4, 1, c_auths[0], InProcessClientConnector(stubs), seq_start=0
        )
        await client.start()
        try:
            for i in range(3):
                await asyncio.wait_for(client.request(b"scrape-%d" % i), 30)
            # f+1 matching replies resolve the client before the LAST
            # replica executes; replica 0 may be one of the stragglers —
            # wait for its counter before scraping (the pre-existing
            # flake this poll fixes fired under PYTHONDEVMODE's slower
            # loop).
            for _ in range(400):
                if replicas[0].metrics.counters.get(
                    "requests_executed", 0
                ) >= 3:
                    break
                await asyncio.sleep(0.02)

            server = MetricsServer(
                lambda: render_families(
                    collect_replica(
                        metrics=replicas[0].metrics,
                        recorder=replicas[0].trace,
                        engine=engine,
                        replica_id=0,
                    )
                ),
                host="127.0.0.1",
            )
            port = server.start()
            try:
                url = f"http://127.0.0.1:{port}/metrics"
                with urllib.request.urlopen(url, timeout=10) as resp:
                    assert resp.status == 200
                    assert resp.headers["Content-Type"] == CONTENT_TYPE
                    body = resp.read().decode()
                assert 'minbft_requests_executed_total{replica="0"} 3' in body
                assert "minbft_stage_latency_seconds_bucket" in body
                assert 'stage="commit_quorum"' in body
                assert "minbft_verify_queue_items_total" in body
                assert "minbft_verify_queue_flushes_total" in body
                assert "minbft_verify_queue_depth" in body

                # the one-shot scrape helper (what `peer metrics` calls)
                scraped = scrape(f"127.0.0.1:{port}")
                assert 'minbft_requests_executed_total{replica="0"} 3' in scraped
                assert "minbft_stage_latency_seconds_bucket" in scraped

                # unknown paths 404 instead of leaking anything
                with pytest.raises(urllib.error.HTTPError):
                    urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/secrets", timeout=10
                    )
                return port, body
            finally:
                server.stop()
        finally:
            await client.stop()
            for r in replicas:
                await r.stop()

    asyncio.run(run())


def test_parse_and_merge_expositions():
    """The scrape→parse→merge round trip (the `peer metrics` cluster
    aggregate): histograms merge EXACTLY (per-le bucket counts add,
    sparse grids union), counters sum, and the per-process replica
    label is stripped so the same logical series folds together."""
    from minbft_tpu.obs.prom import merge_expositions, parse_exposition

    def exposition(replica, counter, samples):
        h = Log2Histogram()
        for v in samples:
            h.observe(v)
        return render_families([
            ("minbft_requests_executed_total", "counter", "c",
             [({"replica": str(replica)}, counter)]),
            ("minbft_stage_latency_seconds", "histogram", "h",
             [({"replica": str(replica), "stage": "execute"}, h)]),
        ])

    a_samples = [1e-6, 3e-6, 1e-3]
    b_samples = [2e-6, 0.25]
    merged = merge_expositions(
        [exposition(0, 3, a_samples), exposition(1, 4, b_samples)]
    )
    fams = parse_exposition(merged)
    assert fams["minbft_requests_executed_total"]["samples"][()] == 7
    hist_fam = fams["minbft_stage_latency_seconds"]
    (key, sample), = hist_fam["samples"].items()
    assert dict(key) == {"stage": "execute"}  # replica label stripped
    assert sample["count"] == 5
    assert sample["sum"] == pytest.approx(sum(a_samples) + sum(b_samples))
    # the merged cumulative counts equal a direct merge of the hists
    both = Log2Histogram()
    for v in a_samples + b_samples:
        both.observe(v)
    cum = 0
    expected = {}
    for i, c in enumerate(both.buckets):
        cum += c
        if c:
            expected[both.bucket_upper_bounds_s()[i]] = cum
    finite = {
        le: c for le, c in sample["buckets"].items() if le != float("inf")
    }
    assert finite == expected


def test_peer_metrics_multi_target_merges(capsys):
    """`peer metrics a b` prints per-target sections plus one merged
    cluster aggregate; --merged-only prints just the aggregate; a dead
    target costs rc=1 but not the live targets' output."""
    from minbft_tpu.sample.peer import cli

    def server_for(replica, count):
        return MetricsServer(
            lambda: render_families([
                ("minbft_requests_executed_total", "counter", "c",
                 [({"replica": str(replica)}, count)]),
            ]),
            host="127.0.0.1",
        )

    s0, s1 = server_for(0, 3), server_for(1, 4)
    p0, p1 = s0.start(), s1.start()
    try:
        rc = cli.main(["metrics", f"127.0.0.1:{p0}", f"127.0.0.1:{p1}"])
        assert rc == 0
        out = capsys.readouterr().out
        assert f"# ==== target 127.0.0.1:{p0} ====" in out
        assert "merged cluster aggregate (2 targets)" in out
        assert 'minbft_requests_executed_total{replica="0"} 3' in out
        assert "\nminbft_requests_executed_total 7" in out

        rc = cli.main([
            "metrics", f"127.0.0.1:{p0}", f"127.0.0.1:{p1}", "--merged-only",
        ])
        assert rc == 0
        out = capsys.readouterr().out
        assert "==== target" not in out
        assert "\nminbft_requests_executed_total 7" in out
    finally:
        s0.stop()
        s1.stop()
    # one target dead: the live one still prints, rc flags the failure
    s2 = server_for(0, 5)
    p2 = s2.start()
    try:
        rc = cli.main(
            ["metrics", f"127.0.0.1:{p2}", f"127.0.0.1:{p1}",
             "--timeout", "0.5"]
        )
        assert rc == 1
        out = capsys.readouterr().out
        assert 'minbft_requests_executed_total{replica="0"} 5' in out
    finally:
        s2.stop()


def test_peer_metrics_subcommand_scrapes(capsys):
    """`peer metrics host:port` prints the exposition text (the scrape
    path an operator uses without any Prometheus server)."""
    from minbft_tpu.sample.peer import cli

    server = MetricsServer(
        lambda: render_families(
            [("minbft_up", "gauge", "smoke", [({}, 1)])]
        ),
        host="127.0.0.1",
    )
    port = server.start()
    try:
        rc = cli.main(["metrics", f"127.0.0.1:{port}"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "minbft_up 1" in out
    finally:
        server.stop()
    # a dead endpoint is a clean error, not a traceback
    rc = cli.main(["metrics", f"127.0.0.1:{port}", "--timeout", "0.5"])
    assert rc == 1
