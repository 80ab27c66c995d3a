"""benchmark/spans.py: the program's timeline reduced to one window.

Synthetic timelines with a known answer for each of the five classes and
their priority; the model's check (a negative residual is reported, not
clipped); a ring that dropped rows gives None; the reduction of one
timeline recorded on the chip; and, on a tiny CPU cluster of
test_bench_run.py's kind, the window found among the client rows against
the harness's own ``Window.opened``."""

import asyncio
import json
import logging
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, observe, run, spans  # noqa: E402
from benchmark import system as sut  # noqa: E402

MS = 1_000_000
SLOT = 10 * MS
COLUMNS = [
    "dispatch_id", "engine", "queue", "kind", "items", "lanes", "reason", "flags",
    "t_first_enqueue", "t_flush", "t_worker_start", "t_prep_end", "t_launch_end",
    "t_result", "t_finish_end", "t_resolved",
]
KERNELS = {
    "ecdsa_verify": types.SimpleNamespace(QUEUE="ecdsa_p256", KIND="verify"),
    "ecdsa_sign": types.SimpleNamespace(QUEUE="ecdsa_p256", KIND="sign"),
}
VERIFY, SIGN = ("ecdsa_p256", "verify"), ("ecdsa_p256", "sign")
T0 = 1_000 * SLOT  # the window opens on a slot boundary, 10 s into the clock


def observations(window_s=0.1, verify_ms=10.0, sign_ms=1.0):
    return observe.Observations(
        window_s=window_s, latencies_ms=[], commits=0, engine_deltas=[],
        device_kind="cpu", platform="cpu", kernels=KERNELS,
        kernel_time_s={"ecdsa_verify": verify_ms / 1e3, "ecdsa_sign": sign_ms / 1e3},
        kernel_dispatches={}, lanes=8, busy_s=None,
    )


def dispatch(k, enq, flush, prep_end, result, kind="verify", flags=0, resolved=None, engine=0,
             queue="ecdsa_p256"):
    """A row with its instants in ms after T0 (the launch call takes 0.5 ms);
    ``queue`` as the engine's stats name it (the ring puts ``sign_`` before a
    sign queue's name)."""
    t = [T0 + round(x * MS) for x in (enq, flush, flush, prep_end, prep_end + 0.5, result, result,
                                      result if resolved is None else resolved)]
    return (k, engine, queue if kind == "verify" else "sign_" + queue, kind, 3, 8, "idle", flags, *t)


def timeline(rows=(), gc=(), idle=(), jax=(), dropped=None, loop_from=0):
    """A timeline with one warm-up request long before the window and the
    window's first request at T0.  ``idle``: {slot after T0: idle ms}."""
    dropped = dropped or {}
    by_engine = {}
    for r in rows:
        by_engine.setdefault(r[1], []).append(r)
    return {
        "dispatch_columns": COLUMNS,
        "dispatch": [
            {"engine": e, "rows": sorted(rs, key=lambda r: r[-1]),
             "dropped": dropped.get("dispatch", 0)}
            for e, rs in sorted(by_engine.items())
        ],
        "gc": {"rows": [(2, T0 + round(a * MS), round((b - a) * MS)) for a, b in gc],
               "dropped": dropped.get("gc", 0)},
        "jax": {"rows": [(n, T0 + round(end * MS), round(d * MS)) for n, end, d in jax],
                "dropped": dropped.get("jax", 0)},
        "client": {"rows": [(0, 1, "start", T0 - 5_000 * MS), (0, 2, "start", T0),
                            (1, 1, "start", T0 + MS)],
                   "dropped": dropped.get("client", 0)},
        "loops": [{"slot_ns": SLOT, "from_ns": loop_from, "current": True,
                   "idle": [(T0 // SLOT + s, round(ms * MS)) for s, ms in dict(idle).items()]},
                  {"slot_ns": SLOT, "from_ns": 0, "current": False,
                   "idle": [(T0 // SLOT + s, SLOT) for s in range(10)]}],
    }


def classes_ms(tl, **kw):
    a = spans.analyse(observations(**kw), tl)
    assert a is not None and a.classes is not None
    return {k: v / MS for k, v in a.classes.items()}


def test_window_is_the_second_start_row_and_window_s_long():
    tl = timeline()
    assert spans.window(observations(0.1), tl) == (T0, T0 + 100 * MS)
    tl["client"]["rows"] = tl["client"]["rows"][:1]  # only the warm-up write
    assert spans.window(observations(0.1), tl) is None


# Each case: one slice of a 100 ms window made to belong to one class, the
# rest of the window a loop wholly busy or wholly idle.
ONE_CLASS = {
    "busy": dict(rows=[dispatch(1, 20, 20, 20, 31)], want={"busy": 10, "loop_busy": 90}),
    "gc": dict(gc=[(30, 55)], want={"gc": 25, "loop_busy": 75}),
    "dispatch_host": dict(rows=[dispatch(1, 40, 40, 48, 59)],
                          want={"dispatch_host": 8, "busy": 10, "loop_busy": 82}),
    "unflushed": dict(rows=[dispatch(1, 10, 25, 25, 36)],
                      want={"unflushed": 15, "busy": 10, "loop_busy": 75}),
    "loop_idle": dict(idle={s: 10 for s in range(10)}, want={"loop_idle": 100}),
    "loop_split_inside_a_slot": dict(idle={3: 4, 4: 10}, want={"loop_idle": 14, "loop_busy": 86}),
}


@pytest.mark.parametrize("case", sorted(ONE_CLASS))
def test_each_class_has_its_known_answer(case):
    spec = dict(ONE_CLASS[case])
    want = spec.pop("want")
    got = classes_ms(timeline(**spec))
    assert {k: v for k, v in got.items() if v} == want


def test_priority_kernel_then_gc_then_dispatch_then_unflushed_then_loop():
    """All five over one stretch: each instant goes to the first that holds."""
    rows = [
        dispatch(1, 0, 0, 0, 11),  # kernel 0-10
        dispatch(2, 5, 30, 40, 51),  # unflushed 5-30, dispatch_host 30-40, kernel 40-50
    ]
    got = classes_ms(timeline(rows=rows, gc=[(8, 20), (35, 45)],
                              idle={s: 10 for s in range(10)}))
    assert got == {
        "busy": 20,  # 0-10 and 40-50, whatever else covers them
        "gc": 10 + 5,  # 10-20 (over unflushed) and 35-40 (over dispatch_host)
        "dispatch_host": 5,  # 30-35
        "unflushed": 10,  # 20-30
        "loop_busy": 0,
        "loop_idle": 50,  # 50-100: nothing queued, the loop asleep
    }


def test_one_chip_runs_one_kernel_at_a_time_from_its_launchs_start():
    rows = [
        dispatch(1, 0, 0, 1, 12, engine=0),
        dispatch(2, 0, 0, 2, 22, engine=1),  # launched while the first runs
        dispatch(3, 0, 0, 3, 23.5, kind="sign", engine=2),
        dispatch(4, 0, 0, 0.5, 60, flags=1),  # a fallback: no kernel of it
    ]
    a = spans.analyse(observations(), timeline(rows=rows))
    kernel_ns = spans.kernel_ns_by_side(observations())
    assert kernel_ns == {VERIFY: 10 * MS, SIGN: MS}
    table = spans.device_intervals(spans.dispatch_rows(timeline(rows=rows), T0), kernel_ns)
    assert [(r["dispatch_id"], (s - T0) / MS, (e - T0) / MS) for r, s, e in table] == [
        (1, 1, 11), (2, 11, 21), (3, 21, 22)]
    assert [x / MS for x in a.device_queue_wait_ns] == [0, 9, 18]
    assert [x / MS for x in a.result_return_ns] == [1, 1, 1.5]
    assert a.classes["busy"] == 21 * MS
    obs = observations()
    obs.__dict__["_spans"] = a
    assert spans.p50_ms(obs, "device_queue_wait_ns") == 9
    assert spans.p50_ms(obs, "result_return_ns") == 1


def test_two_verify_kernels_of_one_configuration_are_each_placed_at_their_own_length():
    """ECDSA messages over HMAC certificates: two verify queues feed the one
    chip, and a row is its own queue's kernel, not its kind's."""
    obs = observations()
    obs.kernels = {**KERNELS, "hmac_verify": types.SimpleNamespace(QUEUE="hmac_sha256", KIND="verify")}
    obs.kernel_time_s = {"ecdsa_verify": 0.010, "ecdsa_sign": 0.001, "hmac_verify": 0.002}
    kernel_ns = spans.kernel_ns_by_side(obs)
    assert kernel_ns == {VERIFY: 10 * MS, SIGN: MS, ("hmac_sha256", "verify"): 2 * MS}
    rows = [
        dispatch(1, 0, 0, 1, 12),
        dispatch(2, 0, 0, 2, 14, queue="hmac_sha256", engine=1),
        dispatch(3, 0, 0, 3, 15, kind="sign", engine=2),
        dispatch(4, 0, 0, 40, 43, queue="hmac_sha256"),
        dispatch(5, 0, 0, 50, 52, kind="sign", queue="ed25519"),  # a side with no kernel's file
    ]
    tl = timeline(rows=rows)
    table = spans.device_intervals(spans.dispatch_rows(tl, T0), kernel_ns)
    assert [(r["dispatch_id"], (s - T0) / MS, (e - T0) / MS) for r, s, e in table] == [
        (1, 1, 11), (2, 11, 13), (3, 13, 14), (4, 40, 42)]
    a = spans.analyse(obs, tl)
    assert a.classes["busy"] == 15 * MS
    assert [x / MS for x in a.device_queue_wait_ns] == [0, 9, 10, 0]
    assert [x / MS for x in a.result_return_ns] == [1, 1, 1, 1]
    assert a.overdrawn == 0
    late = spans.latest_intervals(table)
    assert [(r["dispatch_id"], (e - s) / MS) for r, s, e in late] == [(1, 10), (2, 2), (3, 1), (4, 2)]


def test_of_the_launched_dispatches_the_one_whose_result_came_back_first_ran_first():
    """Launch stamps wait for the interpreter lock and can swap; results
    come back in the device's own order."""
    rows = [
        dispatch(1, 0, 0, 0, 11),
        dispatch(2, 0, 0, 1, 31.5),  # launched second, back last
        dispatch(3, 0, 0, 2, 12.2, kind="sign"),  # launched third, back second
    ]
    a = spans.analyse(observations(), timeline(rows=rows))
    table = spans.device_intervals(spans.dispatch_rows(timeline(rows=rows), T0),
                                   spans.kernel_ns_by_side(observations()))
    assert [(r["dispatch_id"], (s - T0) / MS, (e - T0) / MS) for r, s, e in table] == [
        (1, 0, 10), (3, 10, 11), (2, 11, 21)]
    assert a.negative_residual_share == 0
    # plain FIFO on the launch's start reads -8.8 ms for the sign ...
    fifo = spans.device_intervals(spans.dispatch_rows(timeline(rows=rows), T0),
                                  spans.kernel_ns_by_side(observations()), order="launch")
    assert [(r["dispatch_id"], (s - T0) / MS, (e - T0) / MS) for r, s, e in fifo] == [
        (1, 0, 10), (2, 10, 20), (3, 20, 21)]
    assert [x / MS for x in spans.residuals(fifo)] == [1, 11.5, pytest.approx(-8.8)]
    assert a.launch_order_negative_share == pytest.approx(1 / 3)
    assert [x / MS for x in a.launch_order_queue_wait_ns] == [0, 9, 18]
    # ... but the device is busy at the same instants whatever it picks
    assert spans.union_ns((s, e) for _r, s, e in fifo) == spans.union_ns((s, e) for _r, s, e in table)
    assert a.overdrawn == 0


def test_a_result_back_before_any_order_could_have_run_it_is_overdrawn():
    """The order-free check: the work whose results are back by an instant
    against the time the modelled device has been busy by then."""
    kernel_ns = spans.kernel_ns_by_side(observations())

    def excess_ms(rows):
        table = spans.device_intervals(spans.dispatch_rows(timeline(rows=rows), T0), kernel_ns)
        return [x / MS for x in spans.overdrawn(table)]

    # back-to-back kernels from 1 ms on; every result 1 ms after its kernel
    sound = [dispatch(1, 0, 0, 1, 12), dispatch(2, 0, 0, 2, 22), dispatch(3, 0, 0, 3, 23, kind="sign")]
    assert excess_ms(sound) == [-1, -1, 0]  # the last: all the work there is, all done
    # swapped stamps cost the result order a negative residual, but no excess:
    swapped = [dispatch(1, 0, 0, 0, 11), dispatch(2, 0, 0, 1, 12.2, kind="sign"), dispatch(3, 0, 0, 2, 21.5)]
    assert max(excess_ms(swapped)) <= 0
    # 20 ms of kernels cannot be back 15 ms after the first launch began
    impossible = [dispatch(1, 0, 0, 0, 11), dispatch(2, 0, 0, 1, 15)]
    assert excess_ms(impossible) == [-1, 5]
    a = spans.analyse(observations(), timeline(rows=impossible))
    assert a.overdrawn == 1


def test_latest_placement_ends_each_kernel_at_its_result_or_the_next_ones_start():
    kernel_ns = spans.kernel_ns_by_side(observations())
    rows = [dispatch(1, 0, 0, 1, 14), dispatch(2, 0, 0, 2, 22), dispatch(3, 0, 0, 30, 45, kind="sign")]
    table = spans.device_intervals(spans.dispatch_rows(timeline(rows=rows), T0), kernel_ns)
    late = spans.latest_intervals(table)
    assert [(r["dispatch_id"], (s - T0) / MS, (e - T0) / MS) for r, s, e in late] == [
        (1, 2, 12), (2, 12, 22), (3, 44, 45)]
    late = spans.latest_intervals(table, return_ns=2 * MS)
    assert [(r["dispatch_id"], (s - T0) / MS, (e - T0) / MS) for r, s, e in late] == [
        (1, 0, 10), (2, 10, 20), (3, 42, 43)]


def a_busy_timeline(seed: int) -> tuple:
    """-> (dispatch rows, timeline): 40 dispatches of both kinds over three
    engines, four collector passes and a loop idle by turns, from ``seed``."""
    import random

    rng = random.Random(seed)
    rows, t = [], -30.0
    for k in range(40):
        enq = t + rng.uniform(0, 4)
        flush = enq + rng.uniform(0, 6)
        launch = flush + rng.uniform(0.1, 3)
        rows.append(dispatch(k, enq, flush, launch, launch + rng.uniform(1, 30),
                             kind=rng.choice(["verify", "sign"]), engine=k % 3))
        t = enq
    gc = [(a, a + rng.uniform(1, 12)) for a in (rng.uniform(-5, 100) for _ in range(4))]
    idle = {s: rng.uniform(0, 10) for s in range(-2, 12)}
    return rows, timeline(rows=rows, gc=gc, idle=idle)


@pytest.mark.parametrize("seed", range(4))
def test_the_five_shares_sum_to_one_minus_busy_over_window(seed):
    rows, tl = a_busy_timeline(seed)
    a = spans.analyse(observations(verify_ms=7.3, sign_ms=0.9), tl)
    assert sum(a.classes.values()) == a.window_ns  # every instant is in one class
    shares = sum(a.classes[c] for c in spans.CLASSES) / a.window_ns
    assert abs(shares - (1 - a.classes["busy"] / a.window_ns)) < 1e-9
    assert all(v >= 0 for v in a.classes.values())
    # when the device is busy does not hang on the order it is given
    kernel_ns = spans.kernel_ns_by_side(observations(verify_ms=7.3, sign_ms=0.9))
    dev = spans.dispatch_rows(timeline(rows=rows), T0)
    by_result, by_launch = (spans.device_intervals(dev, kernel_ns, order=o) for o in ("result", "launch"))
    inside = [spans.union_ns(spans._clipped(((s, e) for _r, s, e in t), a.opened, a.closed))
              for t in (by_result, by_launch)]
    assert inside[0] == inside[1] == a.classes["busy"]


def test_the_breakdowns_idle_rows_are_the_five_classes_to_the_nanosecond(monkeypatch):
    """``breakdown.idle_gaps`` of a traced run: a row a class, named as its
    ``device.idle_<class>_share`` metric, largest first, summing to the window
    less the modelled kernels' time; one unattributed row where a ring lost
    rows inside the window."""
    _rows, tl = a_busy_timeline(1)
    monkeypatch.setattr(spans, "timeline", lambda: tl)
    obs = observations(verify_ms=1.0, sign_ms=0.3)
    obs.busy_s = 0.05
    gaps = run.idle_gaps(obs)
    a = spans.analysis(obs)
    assert [name for name, _s in gaps] == sorted(
        (f"idle_{c}" for c in spans.CLASSES), key=lambda n: -a.classes[n[len("idle_"):]])
    assert [s for _n, s in gaps] == sorted((s for _n, s in gaps), reverse=True)
    assert all(round(s * 1e9) == a.classes[n[len("idle_"):]] for n, s in gaps)
    assert sum(round(s * 1e9) for _n, s in gaps) == a.window_ns - a.classes["busy"]
    assert 0 < a.classes["busy"] < a.window_ns and min(s for _n, s in gaps) > 0
    # the shares the metrics report are the same rows over the window
    for name, seconds in gaps:
        assert spans.idle_share(obs, name[len("idle_"):]) == pytest.approx(seconds / obs.window_s, abs=1e-12)
    lost = timeline(rows=[dispatch(1, 20, 20, 20, 31)], gc=[(40, 50)], dropped={"gc": 3})
    monkeypatch.setattr(spans, "timeline", lambda: lost)
    obs = observations()
    obs.busy_s = 0.01
    assert run.idle_gaps(obs) == [[run.UNATTRIBUTED, pytest.approx(0.09)]]


def test_the_kernel_stores_load_time_is_summed_over_its_kernels(monkeypatch):
    """``setup.kernel_load_s``: ``load_s`` summed over the store's rows; 0
    where the process met no store (the CPU backend), None where the program
    keeps no such counter."""
    read = manifest.by_name(REPO, "layer_metrics", "setup.kernel_load_s", "reader").read
    tl = timeline()
    row = {"loads": 1, "builds": 0, "off_main_loads": 0, "load_s": 3.5, "deserialize_s": 3.25}
    tl["jax"]["kernel_store"] = {"_verify_one_packed": row, "_kg_comb_widen": {**row, "load_s": 0.25}}
    monkeypatch.setattr(spans, "timeline", lambda: tl)
    assert read(observations()) == 3.75
    tl["jax"]["kernel_store"] = {}
    assert read(observations()) == 0
    del tl["jax"]["kernel_store"]
    assert read(observations()) is None


def test_a_negative_residual_is_reported_not_clipped():
    # the result came back 4 ms before the modelled kernel could have ended
    rows = [dispatch(1, 0, 0, 1, 7), dispatch(2, 0, 0, 20, 31)]
    a = spans.analyse(observations(), timeline(rows=rows))
    assert [x / MS for x in a.result_return_ns] == [-4, 1]
    assert a.negative_residual_share == 0.5
    obs = observations()
    obs.__dict__["_spans"] = a
    assert spans.p50_ms(obs, "result_return_ns") == -1.5


@pytest.mark.parametrize("ring,lost", [
    ("dispatch", ["classes", "device_queue_wait_ns", "loop_wake_ns", "result_return_ns"]),
    ("gc", ["classes"]),
    ("jax", ["jax_in_window_ns", "jax_trace_before_ns"]),
    ("loop", ["classes", "loop_idle_ns"]),
])
def test_a_ring_that_dropped_rows_inside_the_window_gives_none(ring, lost):
    rows = [dispatch(1, 20, 20, 20, 31)]
    whole = spans.analyse(observations(), timeline(rows=rows, gc=[(40, 50)]))
    fields = ["classes", "loop_idle_ns", "device_queue_wait_ns", "loop_wake_ns",
              "result_return_ns", "jax_in_window_ns", "jax_trace_before_ns"]
    assert all(getattr(whole, f) is not None for f in fields)
    if ring == "loop":  # its record starts after the window opened
        tl = timeline(rows=rows, gc=[(40, 50)], loop_from=T0 + 1)
    else:  # its oldest surviving row was written inside the window
        tl = timeline(rows=rows, gc=[(40, 50)], dropped={ring: 3})
    a = spans.analyse(observations(), tl)
    assert {f for f in fields if getattr(a, f) is None} == set(lost)
    obs = observations()
    obs.__dict__["_spans"] = a
    if "classes" in lost:
        assert all(spans.idle_share(obs, c) is None for c in spans.CLASSES)
    # rows dropped before the window opened cost nothing
    early = timeline(rows=[dispatch(0, -90, -90, -90, -79)] + rows, gc=[(-50, -40), (40, 50)],
                     dropped={"dispatch": 3, "gc": 3})
    assert spans.analyse(observations(), early).classes == whole.classes


def test_dropped_client_rows_or_a_program_without_a_timeline_give_nothing(monkeypatch):
    obs = observations()
    assert spans.analyse(obs, timeline(dropped={"client": 1})) is None
    assert spans.analyse(obs, None) is None
    monkeypatch.setattr(spans, "timeline", lambda: None)  # the parent program
    for name in sorted(os.listdir(os.path.join(manifest.HERE, "layer_metrics"))):
        module = manifest.load_module(os.path.join(manifest.HERE, "layer_metrics", name))
        if module.DECLARATION["source"] == "program_span" and "spans" in vars(module):
            assert module.read(observations()) is None, name


def test_jax_events_inside_the_window_and_tracing_before_it():
    trace, compile_ = spans.JAXPR_TRACE, "/jax/core/compile/backend_compile_duration"
    jax = [
        (trace, -1000, 3000),  # set-up: an outer trace of 3 s ...
        (trace, -2000, 500),  # ... with a nested one inside it: counted once
        (compile_, -500, 400),  # not tracing
        (trace, 2, 5),  # straddles the opening: 3 ms before, 2 ms inside
        (compile_, 60, 10),  # inside the window
    ]
    a = spans.analyse(observations(), timeline(jax=jax))
    assert a.jax_trace_before_ns == (3000 + 3) * MS
    assert a.jax_in_window_ns == (2 + 10) * MS
    quiet = spans.analyse(observations(), timeline())
    assert quiet.jax_in_window_ns == 0  # read, and 0: not None


def test_recorded_chip_timeline_reduces_to_its_recorded_numbers():
    """One window of n3f1-ecdsa.closed-16x8 recorded on the chip (PR 26),
    cut to a few seconds: the reduction gives the numbers written down
    beside it when it was recorded."""
    with open(os.path.join(manifest.HERE, "recorded", "timeline_n3f1_pr26.json")) as fh:
        doc = json.load(fh)
    obs = observations(window_s=doc["window_s"])
    obs.kernel_time_s = doc["kernel_time_s"]
    a = spans.analyse(obs, doc["timeline"])
    assert a is not None and a.classes is not None
    assert sum(a.classes.values()) == a.window_ns
    for name, want in doc["expected"]["classes_ns"].items():
        assert a.classes[name] == want, name
    assert a.loop_idle_ns == doc["expected"]["loop_idle_ns"]
    assert len(a.result_return_ns) == doc["expected"]["dispatches"]
    assert a.negative_residual_share == pytest.approx(doc["expected"]["negative_residual_share"])
    assert a.jax_in_window_ns == doc["expected"]["jax_in_window_ns"]
    obs.__dict__["_spans"] = a
    for field, want in doc["expected"]["p50_ms"].items():
        assert spans.p50_ms(obs, field) == pytest.approx(want)


def test_recorded_chip_timeline_passes_the_checks_that_do_not_lean_on_the_order():
    """Result order minimises lateness, so its negative share is fitted.
    What is not: no result is back before any order could have run it;
    plain FIFO on the launch's start gives the same classes to the
    nanosecond and the same median queue wait within a twentieth; and with
    every kernel placed as late as its result allows, instead of as early
    as its launch allows, each class moves by under 0.02 of the window."""
    with open(os.path.join(manifest.HERE, "recorded", "timeline_n3f1_pr26.json")) as fh:
        doc = json.load(fh)
    obs = observations(window_s=doc["window_s"])
    obs.kernel_time_s = doc["kernel_time_s"]
    tl = doc["timeline"]
    a = spans.analyse(obs, tl)
    assert a.overdrawn == 0
    assert a.launch_order_negative_share < 0.01
    by_result = spans.percentile(sorted(a.device_queue_wait_ns), 50)
    by_launch = spans.percentile(sorted(a.launch_order_queue_wait_ns), 50)
    assert abs(by_launch - by_result) < 0.05 * by_result

    rows = spans.dispatch_rows(tl, a.opened)
    kernel_ns = spans.kernel_ns_by_side(obs)
    loop = next(lp for lp in tl["loops"] if lp.get("current"))

    def classes(table):
        return spans.attribute(
            a.opened, a.closed, busy=[(s, e) for _r, s, e in table],
            gc=[(t, t + d) for _g, t, d in tl["gc"]["rows"]],
            dispatch_host=[(r["t_flush"], r["t_prep_end"]) for r in rows],
            unflushed=[(r["t_first_enqueue"], r["t_flush"]) for r in rows],
            slot_ns=loop["slot_ns"], idle_by_slot=dict(loop["idle"]),
        )

    early = spans.device_intervals(rows, kernel_ns)
    assert classes(early) == a.classes
    assert classes(spans.device_intervals(rows, kernel_ns, order="launch")) == a.classes
    for return_ns in (0, 2 * MS):  # 2 ms: the quiet return latency read by hand
        late = classes(spans.latest_intervals(early, return_ns))
        moved = {c: abs(late[c] - a.classes[c]) / a.window_ns for c in spans.CLASSES}
        assert max(moved.values()) < 0.02, moved


# -- on a live cluster -----------------------------------------------------

CELL = "n3f1-ecdsa.closed-16x8"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "rehearsal": True}


@pytest.fixture(scope="module")
def live():
    """A tiny CPU cluster driven through one of the run's own windows ->
    (the window, the timeline with the client rows of this cluster only,
    the window's Observations)."""
    from minbft_tpu.obs import trace as obs_trace

    cell = manifest.load_cell(CELL)

    async def everything():
        config, mix = run.sized(cell, CPU)
        mark = len(obs_trace.timeline()["client"]["rows"])
        system = await sut.build(cell, config, mix.clients, on_cpu=True)
        try:
            got = await run.one_window(system, mix, 2**31 + 26, 1.0)
            tl = spans.timeline()
        finally:
            await system.stop()
        tl["client"]["rows"] = tl["client"]["rows"][mark:]
        return got, tl

    logging.disable(logging.WARNING)
    try:
        got, tl = asyncio.run(everything())
    finally:
        logging.disable(logging.NOTSET)
    obs = observations(window_s=1.0, verify_ms=0.001, sign_ms=0.001)
    return got, tl, obs


def test_window_opens_where_the_harness_opened_it(live):
    got, tl, obs = live
    opened, closed = spans.window(obs, tl)
    # time.perf_counter and time.monotonic_ns are one clock on Linux
    assert abs(opened / 1e9 - got["window"].opened) < 0.020
    assert closed - opened == 1_000_000_000
    stages = [stage for _c, _s, stage, _t in tl["client"]["rows"]]
    assert stages == ["start"] * (1 + len(got["window"].issued))


def test_live_timeline_reduces_with_every_instant_in_one_class(live):
    got, tl, obs = live
    a = spans.analyse(obs, tl)
    assert a.classes is not None and sum(a.classes.values()) == a.window_ns
    counted = sum(d["verify_batches"] + d["sign_batches"] for d in got["deltas"])
    assert counted > 0 and abs(len(a.result_return_ns) - counted) <= 4  # the window's edges
    assert 0 < a.loop_idle_ns < a.window_ns
    assert a.jax_in_window_ns == 0 and a.jax_trace_before_ns >= 0
    assert min(a.loop_wake_ns) >= 0
