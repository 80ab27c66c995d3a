"""The flagship at upstream's timers with its primary crashed under load
(``n7f3-ecdsa-2s1s.closed-16x8-primary-crash``), the first open-loop cell
(``n7f3-ecdsa.open-0.8knee``), and the two readers of the program's
view-change rows: the files against what they copy, the readers over
timelines made by hand, and one rehearsal of the crash cell through the
run's own window and comparison on the CPU backend."""

import json
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import manifest, run, spans  # noqa: E402
from benchmark.generator import Fault, Mix  # noqa: E402
from test_bench_faults import a_run, drive  # noqa: E402  (this directory)
from test_bench_manifest import hold_the_schemes, per_layer_of  # noqa: E402  (this directory)

MANIFEST = manifest.load_manifest()
CRASH_CELL = "n7f3-ecdsa-2s1s.closed-16x8-primary-crash"
OPEN_CELL = "n7f3-ecdsa.open-0.8knee"
READERS = ("protocol.viewchange_ms", "protocol.viewchange_device_items")


def reader(name):
    return manifest.by_name(REPO, "layer_metrics", name, "reader")


# -- the files ----------------------------------------------------------------


def test_the_configuration_is_the_flagships_at_upstreams_two_timers():
    cell, flagship = manifest.load_cell(CRASH_CELL), manifest.load_cell("n7f3-ecdsa.closed-16x8")
    config, entry = cell.config, next(c for c in MANIFEST["configs"] if c["name"] == cell.config_name)
    assert (config["timeout_request"], config["timeout_prepare"], config["timeout_viewchange"]) == (2.0, 1.0, 8.0)
    changed = {"name", "source", "timeout_request", "timeout_prepare", "timeout_viewchange",
               "reduced", "assumed"}
    assert {k: v for k, v in config.items() if k not in changed} == {
        k: v for k, v in flagship.config.items() if k not in changed}
    assert set(entry["reduced"]) == set(config["reduced"]) == {"hosts"}
    assert config["reduced"]["hosts"] == flagship.config["reduced"]["hosts"]
    assert "timeout_viewchange" in config["assumed"] and "consensus.yaml" in entry["source"]
    assert "configs[2]" in entry["source"] and cell.chips == 1


def test_the_crash_traffic_is_closed_16x8_with_one_crash_of_the_primary():
    cell = manifest.load_cell(CRASH_CELL)
    closed = manifest.load_cell("n7f3-ecdsa.closed-16x8").traffic
    assert {k: v for k, v in cell.traffic.items() if k not in ("faults", "note")} == {
        k: v for k, v in closed.items() if k != "note"}
    mix = Mix.from_file(cell.traffic)
    assert mix.faults == (Fault(10.0, "crash", "primary"),)
    assert set(mix.against(cell.config)) == {"crash"}


def test_the_open_loop_file_is_named_for_its_rate():
    cell = manifest.load_cell(OPEN_CELL)
    mix = Mix.from_file(cell.traffic)
    assert cell.traffic_name == f"open-{mix.rate_rps:g}" and mix.rate_rps % 5 == 0
    assert (mix.loop, mix.clients, mix.payload_bytes, mix.ack_wait_s, mix.faults) == (
        "open", 16, 35, 60.0, ())
    assert (mix.forged_request_every, mix.forged_reply_every) == (64, 64)
    assert cell.config == manifest.load_cell("n7f3-ecdsa.closed-16x8").config


@pytest.mark.parametrize("name", [CRASH_CELL, OPEN_CELL])
def test_each_new_cell_reports_the_flagships_metrics(name):
    """Every entry that applies by the manifest's one rule, whatever their
    number: the flagship's metrics that read a number in these cells
    (``engine.full_flush_share`` among them), and the view-change readers
    and ``outage_ms`` in the cell with a fault schedule alone."""
    cell = manifest.load_cell(name)
    names = [m.name for m in cell.per_layer]
    assert names == per_layer_of(MANIFEST, name)
    hold_the_schemes(cell)
    assert {"engine.full_flush_share", "device.idle_share", "kernel.ecdsa_verify_roofline",
            "client.finality_p50_ms", "setup.jax_trace_s"} <= set(names)
    assert (set(READERS) <= set(names)) == (name == CRASH_CELL)


def test_the_outage_is_the_longest_stretch_without_an_ack_and_absent_without_a_fault():
    """``outage_ms``: the longest stretch that began inside the window with no
    write acknowledged (the note ``longest_ack_gap_ms``), in a window in
    which a fault was applied; in one without, not reported, and not 0."""
    cell = manifest.load_cell(CRASH_CELL)
    acks = [100.0 + 0.5 * k for k in range(21)] + [113.9 + 0.5 * k for k in range(32)] + [131.0]
    window = types.SimpleNamespace(
        opened=100.0, closed=130.0, faults_applied=[object()],
        issued=[types.SimpleNamespace(acked=t) for t in acks] + [types.SimpleNamespace(acked=None)])
    got = {"latencies_ms": [1.0, 2.0, 3.0], "commits": 52, "window": window}
    metrics = run.end_to_end(cell, got, 30.0, 15.0)
    assert list(metrics) == [m["name"] for m in cell.end_to_end]
    assert metrics["outage_ms"] == {"value": pytest.approx(3900.0), "unit": "ms"}
    assert run.longest_ack_gap_ms(window) == metrics["outage_ms"]["value"]
    window.faults_applied = []
    assert set(run.end_to_end(cell, got, 30.0, 15.0)) == {
        m["name"] for m in cell.end_to_end} - {"outage_ms"}
    # the other cells do not take it
    assert all("outage_ms" not in {m["name"] for m in manifest.load_cell(w["name"]).end_to_end}
               for w in MANIFEST["workloads"] if w["name"] != CRASH_CELL)


# -- the readers, over timelines made by hand ---------------------------------

MS = 1_000_000
OPENED = 5_000 * MS  # the second client start: the window opens
WINDOW_S = 30.0


def a_timeline(rows=(), items=(), dropped=0, verify_dropped=0, section=True):
    tl = {"client": {"rows": [(0, 1, "start", 1_000 * MS), (0, 2, "start", OPENED)], "dropped": 0}}
    if section:
        tl["viewchange"] = {"rows": list(rows), "dropped": dropped,
                            "verify_items": list(items), "verify_dropped": verify_dropped}
    return tl


def at(s):
    return OPENED + round(s * 1e3) * MS


# the crash cell's shape: replica 0 down at 10 s, six survivors in view 1
ONE = [(r, 1, "demand", at(12.0 + 0.01 * r)) for r in (1, 2, 3, 4)] + [
    (r, 1, "started", at(12.1)) for r in range(1, 7)] + [
    (1, 1, "new_view_sent", at(12.9))] + [
    (r, 1, "entered", at(13.0 + 0.1 * r)) for r in range(1, 7)]
ONE_ITEMS = [(r, 1, 100 + r, at(12.5)) for r in range(1, 7)]


def read_both(monkeypatch, tl):
    monkeypatch.setattr(spans, "timeline", lambda: tl)
    obs = types.SimpleNamespace(window_s=WINDOW_S)
    return tuple(reader(name).read(obs) for name in READERS)


def test_one_view_change_reads_from_the_first_demand_to_the_last_entry(monkeypatch):
    ms, items = read_both(monkeypatch, a_timeline(ONE, ONE_ITEMS))
    assert ms == pytest.approx(13.6e3 - 12.01e3)
    assert items == sum(100 + r for r in range(1, 7))


def test_a_crashed_replicas_rows_are_not_read(monkeypatch):
    """A replica that never entered the view is down: a demand its timer sent
    after it went, and the checks it made, are no part of the view change."""
    stale = [(0, 1, "demand", at(11.0))]
    ms, items = read_both(monkeypatch, a_timeline(stale + ONE, ONE_ITEMS + [(0, 1, 999, at(11.5))]))
    assert ms == pytest.approx(13.6e3 - 12.01e3)
    assert items == sum(100 + r for r in range(1, 7))


def test_no_view_change_in_the_window_reads_none(monkeypatch):
    assert read_both(monkeypatch, a_timeline()) == (None, None)
    # begun before the window opened: not this window's
    early = [(r, v, s, t - 20_000 * MS) for r, v, s, t in ONE]
    assert read_both(monkeypatch, a_timeline(early, ONE_ITEMS)) == (None, None)
    # begun inside it and ended after it closed: the window's
    late = [(r, v, s, t + 17_000 * MS) if s == "entered" else (r, v, s, t) for r, v, s, t in ONE]
    assert read_both(monkeypatch, a_timeline(late, ONE_ITEMS))[0] == pytest.approx(13.6e3 + 17e3 - 12.01e3)
    # begun and never ended
    unfinished = [row for row in ONE if row[2] != "entered"]
    assert read_both(monkeypatch, a_timeline(unfinished, ONE_ITEMS)) == (None, None)


def test_an_earlier_clusters_rows_are_not_read(monkeypatch):
    """A process that ran another cluster before the window (a test process)
    keeps its rows: the same replica ids in the same view, and its checks."""
    before = [(r, v, s, t - 20_000 * MS) for r, v, s, t in ONE if r in (1, 2)]
    ms, items = read_both(monkeypatch, a_timeline(before + ONE, [(1, 1, 555, at(-8.0))] + ONE_ITEMS))
    assert ms == pytest.approx(13.6e3 - 12.01e3)
    assert items == sum(100 + r for r in range(1, 7))


def test_the_first_of_two_view_changes_is_read(monkeypatch):
    second = [(r, 2, s, t + 5_000 * MS) for r, _v, s, t in ONE if r != 1]
    ms, items = read_both(monkeypatch, a_timeline(ONE + second, ONE_ITEMS + [(2, 2, 7, at(17.5))]))
    assert ms == pytest.approx(13.6e3 - 12.01e3) and items == sum(100 + r for r in range(1, 7))


def test_a_program_without_the_section_or_a_ring_that_lost_rows_reads_none(monkeypatch):
    assert read_both(monkeypatch, a_timeline(section=False)) == (None, None)
    assert read_both(monkeypatch, a_timeline(ONE, ONE_ITEMS, dropped=3)) == (None, None)
    assert read_both(monkeypatch, a_timeline(ONE, ONE_ITEMS, verify_dropped=1)) == (None, None)
    # a ring that dropped rows older than the window still holds the window's
    kept = [(4, 0, "entered", OPENED - MS)] + ONE
    assert read_both(monkeypatch, a_timeline(kept, ONE_ITEMS, dropped=3))[0] == pytest.approx(1590.0)
    kept_items = [(4, 0, 9, OPENED - MS)] + ONE_ITEMS
    assert read_both(monkeypatch, a_timeline(ONE, kept_items, verify_dropped=3))[1] == sum(100 + r for r in range(1, 7))
    monkeypatch.setattr(spans, "timeline", lambda: None)
    assert reader(READERS[0]).read(types.SimpleNamespace(window_s=WINDOW_S)) is None


def test_the_readers_declare_what_a_later_entry_of_the_benchmark_will_say():
    """What their entries say: they move the outage, the crash cell's own
    end-to-end metric, and are read in that cell alone."""
    assert reader(READERS[0]).DECLARATION == {
        "unit": "ms", "better": "lower", "source": "program_span",
        "layer": "protocol", "moves": "outage_ms"}
    assert reader(READERS[1]).DECLARATION == {
        "unit": "items", "better": "lower", "source": "program_counter",
        "layer": "protocol", "moves": "outage_ms"}
    for name in READERS:
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert CRASH_CELL in entry["workloads"] and OPEN_CELL not in entry["workloads"]


# -- a rehearsal of the crash cell on the CPU backend --------------------------


def test_the_crash_cell_rehearsed_ends_correct_in_view_1():
    """The cell's own configuration and schedule at the rehearsal's size: the
    primary crashes 10 s into a 14 s window, the six survivors change view
    through their engines, and every write is answered in view 1."""
    cell = manifest.load_cell(CRASH_CELL)

    async def read(system, mix):  # the window's readers, over the timeline as it is now
        obs = types.SimpleNamespace(window_s=14.0)
        return {name: reader(name).read(obs) for name in READERS}

    (result, seen), read_out = drive(cell, [a_run(cell, 2**31 + 3600, 14.0), read], 200)
    assert result["correct"] is True, json.dumps(result["compared"])
    assert not any(line["value"] for line in result["compared"].values())
    assert seen["views"][1:] == [1] * 6
    order = seen["chains"][1]
    assert all(chain == order for chain in seen["chains"][1:])
    gone = seen["chains"][0]
    assert 0 < len(gone) < len(order) and gone == order[:len(gone)]
    notes = result["notes"]
    assert [f["replica"] for f in notes["faults_applied"]] == [0]
    # the outage holds the view change, and the request timers that ran out
    # before it (each armed when its write reached a backup, before the crash)
    assert 0 < read_out["protocol.viewchange_ms"] < notes["longest_ack_gap_ms"] < 60e3
    # the end-to-end metric is that note, in this cell alone
    assert result["metrics"]["outage_ms"] == {"value": notes["longest_ack_gap_ms"], "unit": "ms"}
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert read_out["protocol.viewchange_device_items"] > 0
