"""A deployment with a failure in it: the fault schedule of a traffic file,
the comparison's rules with replicas down, and what the run says the failure
cost.  The rules are held first over stand-in ledgers and replicas, with no
cluster at all, so they hold whatever the program does; then a later PR's
tree (files alone: a configuration at short timers, traffic files with a
schedule) is driven on the CPU backend through the run's own window,
comparison and result line, a cluster a scenario, each under its own time
limit.  No case calibrates a device trace."""

import asyncio
import dataclasses
import json
import logging
import os
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import compare, controls, manifest, run  # noqa: E402
from benchmark import system as sut  # noqa: E402
from benchmark.generator import Applied, Fault, Mix, target_replica  # noqa: E402
from bench_timeline import timeline_from_here  # noqa: E402  (this directory)
from test_bench_deployments import later_checkout  # noqa: E402  (this directory)

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "rehearsal": True}
SEED = 2**31 + 3500
ACCEPTED = manifest.load_cell("n3f1-ecdsa.closed-16x8")
# A round of the rehearsal's four writes takes 0.65-0.9 s on this sandbox's CPU
# (8-lane kernels, loop lowering), so a request timer of 1 s would depose a
# sound primary on a slower or busier machine: 3 s / 1.5 s keep the view
# changes to those the schedule explains.
TIMERS = {"timeout_request": 3.0, "timeout_prepare": 1.5, "timeout_viewchange": 8.0}
CRASH_AT_S = 1.5


# -- the schedule is data -------------------------------------------------------


def test_a_traffic_file_without_faults_gives_todays_mix():
    mix = Mix.from_file(ACCEPTED.traffic)
    assert "faults" not in ACCEPTED.traffic and mix.faults == ()
    assert mix == Mix.from_file({**ACCEPTED.traffic, "faults": []})
    today = {f.name: ACCEPTED.traffic[f.name] for f in dataclasses.fields(Mix) if f.name != "faults"}
    assert mix == Mix(**today)
    assert mix.against(ACCEPTED.config) == {}


def test_a_schedule_loads_in_the_order_it_fires():
    mix = Mix.from_file({**ACCEPTED.traffic, "faults": [
        {"at_s": 20, "kind": "crash", "target": 2}, {"at_s": 10.5, "kind": "crash", "target": "primary"}]})
    assert mix.faults == (Fault(10.5, "crash", "primary"), Fault(20.0, "crash", 2))
    seven = manifest.load_cell("n7f3-ecdsa.closed-16x8").config
    assert callable(mix.against(seven)["crash"].apply)


@pytest.mark.parametrize("entry,why", [
    ({"at_s": 1, "kind": "crash"}, "at_s, kind, target"),
    ({"at_s": -1, "kind": "crash", "target": 0}, "at_s"),
    ({"at_s": "soon", "kind": "crash", "target": 0}, "at_s"),
    ({"at_s": 1, "kind": "../crash", "target": 0}, "kind"),
    ({"at_s": 1, "kind": "crash", "target": "leader"}, "target"),
    ({"at_s": 1, "kind": "crash", "target": -1}, "target"),
], ids=["key_missing", "before_the_window", "no_number", "no_name", "no_role", "no_replica"])
def test_a_malformed_fault_is_refused(entry, why):
    with pytest.raises(manifest.BenchmarkError, match=why):
        Mix.from_file({**ACCEPTED.traffic, "faults": [entry]})


def test_more_crashes_than_f_are_refused_before_any_cluster_starts():
    two = [{"at_s": 1, "kind": "crash", "target": "primary"}, {"at_s": 2, "kind": "crash", "target": "backup"}]
    mix = Mix.from_file({**ACCEPTED.traffic, "faults": two})
    with pytest.raises(manifest.BenchmarkError, match=r"crashes 2 replicas .* f = 1: they promise nothing beyond f"):
        mix.against(ACCEPTED.config)
    assert set(mix.against(manifest.load_cell("n7f3-ecdsa.closed-16x8").config)) == {"crash"}
    with pytest.raises(manifest.BenchmarkError, match="replicas 0 to 2"):
        Mix.from_file({**ACCEPTED.traffic, "faults": [{"at_s": 1, "kind": "crash", "target": 3}]}).against(
            ACCEPTED.config)


def test_a_kind_without_a_file_or_without_a_rule_is_refused_by_name(tmp_path):
    mix = Mix.from_file({**ACCEPTED.traffic, "faults": [{"at_s": 1, "kind": "partition", "target": 0}]})
    with pytest.raises(manifest.BenchmarkError, match=r"benchmark/faults/partition\.py"):
        mix.against(ACCEPTED.config)
    # a later PR's file for it is found, and refused until the comparison can judge it
    cell = later_checkout(tmp_path, "n3f1-later", "ecdsa-p256", "SOFT_ECDSA",
                          {"ecdsa_verify": None, "ecdsa_sign": None})
    (tmp_path / "benchmark" / "faults" / "partition.py").write_text(
        "async def apply(system, replica_id):\n    pass\n")
    with pytest.raises(manifest.BenchmarkError, match=r"compare\.py has no rule yet"):
        mix.against(cell.config, cell.root)


def test_the_run_refuses_such_a_cell_before_it_builds_anything(tmp_path, monkeypatch):
    cells = faulty_checkout(tmp_path, {"closed-two-crashes": {"faults": [
        {"at_s": 1, "kind": "crash", "target": "primary"}, {"at_s": 2, "kind": "crash", "target": "backup"}]}})
    monkeypatch.setattr(sut, "build", None)  # never reached
    with pytest.raises(manifest.BenchmarkError, match="nothing beyond f"):
        run.sized(cells["closed-two-crashes"], CPU)


# -- the comparison's rules, over stand-ins --------------------------------------


def chain(*names: str) -> list:
    return [name.encode() for name in names]


def digest_of(payloads) -> bytes:
    return compare.replay(payloads)[0]


def off(chains, gone=(), digests=None):
    return compare.ledgers_off_reference(
        chains, digests or [digest_of(c) for c in chains], gone)[0]


AGREED = chain("a", "b", "c", "d")


@pytest.mark.parametrize("chains,gone,want", [
    ([AGREED, AGREED, AGREED], (), 0),
    ([AGREED, AGREED, AGREED[:3]], (), 1),  # a running replica behind is off: converged() waited
    ([AGREED, chain("a", "b", "d", "c"), AGREED], (), 1),
    ([AGREED[:2], AGREED, AGREED], (0,), 0),  # down, and a prefix of what the others agreed on
    ([[], AGREED, AGREED], (0,), 0),
    ([chain("a", "x"), AGREED, AGREED], (0,), 1),  # down, having executed what nobody agreed on
    ([AGREED + chain("e"), AGREED, AGREED], (0,), 1),  # down, and ahead of every running replica
    ([AGREED[:2], AGREED, chain("a", "b", "c", "x")], (0,), 1),  # down does not excuse the running
    ([AGREED + chain("e", "f"), AGREED, AGREED[:1]], (0,), 2),  # the order is a RUNNING replica's
], ids=["all_equal", "running_behind", "running_reordered", "crashed_prefix", "crashed_empty",
        "crashed_diverged", "crashed_ahead", "running_off_beside_a_crash", "order_from_the_running"])
def test_ledgers_are_held_to_the_running_replicas_order(chains, gone, want):
    assert off(chains, gone) == want


def test_a_state_digest_off_its_chain_counts_running_or_down():
    sound = [digest_of(AGREED)] * 3
    assert off([AGREED] * 3, (), sound) == 0
    assert off([AGREED] * 3, (), [sound[0], b"\0" * 32, sound[2]]) == 1
    assert off([AGREED[:2], AGREED, AGREED], (0,), [digest_of(AGREED[:2])] + sound[1:]) == 0
    assert off([AGREED[:2], AGREED, AGREED], (0,), sound) == 1  # the digest of more than it executed


@pytest.mark.parametrize("n,crashed,view", [
    (3, [], 0), (3, [0], 1), (3, [2], 0), (3, [1], 0),
    (7, [0, 1], 2), (7, [1, 0], 2),  # view 1's primary was down already: the cluster passes it by
    (7, [0, 2, 1], 3), (7, [3, 5, 6], 0), (7, [0, 6, 1], 2),
], ids=str)
def test_the_view_that_crashes_explain(n, crashed, view):
    assert compare.view_explained(n, crashed) == view


@pytest.mark.parametrize("views,crashed,unexplained,want", [
    ([0, 0, 0], [], [], 0),
    ([1, 1, 1], [], [], 3),  # nobody down: the views summed, as before there were schedules
    ([0, 1, 1], [0], [], 0),  # as explained (what the crashed one last said does not count)
    ([0, 2, 2], [0], [], 2),  # one view change too many
    ([0, 0, 0], [0], [], 2),  # one too few
    ([0, 1, 2], [0], [], 1),
    ([0, 0, 0], [2], [], 0),  # a backup's crash explains none
    ([0, 0, 1], [1], [], 1),
    ([0, 1, 1], [], [0], 2),  # down behind the schedule's back: nothing explains view 1
    ([0, 0, 2, 2, 2, 2, 2], [0], [1], 5),
], ids=str)
def test_views_are_held_to_what_the_schedule_explains(views, crashed, unexplained, want):
    assert compare.views_unexplained(views, crashed, unexplained) == want


def test_a_crashed_replicas_engine_owes_the_window_no_work_and_no_fault_is_excused():
    side = ("ecdsa_p256", "verify")
    worked = {"items": {side: 5}}
    idle = {"items": {side: 0}}
    clean = {"verify_timeouts": 0, "sign_timeouts": 0, "sign_fallback": 0, "written_off": 0}
    assert compare.device_path_faults([worked, idle], [clean, clean], [side]) == 1
    assert compare.device_path_faults([worked, idle], [clean, clean], [side], gone=[1]) == 0
    assert compare.device_path_faults([idle, worked], [clean, clean], [side], gone=[1]) == 1
    timed_out = dict(clean, verify_timeouts=1, written_off=1)
    assert compare.device_path_faults([worked, idle], [clean, timed_out], [side], gone=[1]) == 2


def stand_in(n=3, views=(0, 0, 0), applied=(), unexplained=()):
    replicas = [types.SimpleNamespace(metrics=types.SimpleNamespace(current_view=v)) for v in views]
    return types.SimpleNamespace(
        config={"n": n, "f": (n - 1) // 2}, cluster=types.SimpleNamespace(replicas=replicas),
        faults_applied=list(applied), down_unexplained=list(unexplained))


def test_who_is_down_and_whom_a_fault_aims_at():
    system = stand_in()
    assert compare.down(system) == [] and compare.running(system) == [0, 1, 2]
    assert target_replica(system, "primary") == 0 and target_replica(system, "backup") == 2
    assert target_replica(system, 1) == 1
    after = stand_in(views=(0, 1, 1), applied=[Applied("crash", 0, 12.5)])
    assert compare.down(after) == [0] and compare.running(after) == [1, 2]
    assert target_replica(after, "primary") == 1 and target_replica(after, "backup") == 2
    # the view change not over yet: the next in line is the primary to be
    assert target_replica(stand_in(applied=[Applied("crash", 0, 12.5)]), "primary") == 1
    seven = stand_in(7, (0,) * 7, [Applied("crash", 6, 1.0)], unexplained=[5])
    assert compare.down(seven) == [6, 5] and target_replica(seven, "backup") == 4


# -- a later PR's tree, driven on the CPU backend ---------------------------------


def faulty_checkout(root, mixes: dict) -> dict:
    """``later_checkout``'s tree with the configuration ``n3f1-fast`` (n3f1 at
    the timers :data:`TIMERS`) and, for each of ``mixes`` = {traffic name: what it
    changes in closed-16x8}, the traffic's file and its cell -> {traffic name: cell}."""
    later_checkout(root, "n3f1-fast", "ecdsa-p256", "SOFT_ECDSA",
                   {"ecdsa_verify": None, "ecdsa_sign": None})
    path = root / "benchmark" / "configs" / "n3f1-fast.json"
    path.write_text(json.dumps({**json.loads(path.read_text()), **TIMERS}))
    later = json.loads((root / "BENCHMARK.json").read_text())
    for name, changes in mixes.items():
        (root / "benchmark" / "traffic" / f"{name}.json").write_text(
            json.dumps({**ACCEPTED.traffic, **changes}))
        later["workloads"].append({"name": f"n3f1-fast.{name}", "config": "n3f1-fast",
                                   "traffic": name, "chips": 1, "why": "z"})
        for entry in later["per_layer"]:
            if "workloads" in entry:
                entry["workloads"].append(f"n3f1-fast.{name}")
    (root / "BENCHMARK.json").write_text(json.dumps(later))
    return {name: manifest.load_cell(f"n3f1-fast.{name}", root=str(root)) for name in mixes}


def drive(cell, steps, limit: float):
    """Build the cell's cluster at the rehearsal's size and run ``steps`` over
    it, each ``async (system, mix) -> anything``, all within ``limit`` seconds."""
    async def everything():
        config, mix = run.sized(cell, CPU)
        system = await sut.build(cell, config, mix.clients, on_cpu=True)
        try:
            return [await step(system, mix) for step in steps]
        finally:
            await system.stop()

    logging.disable(logging.WARNING)
    try:
        with timeline_from_here():
            return asyncio.run(asyncio.wait_for(everything(), limit))
    finally:
        logging.disable(logging.NOTSET)


def a_run(cell, seed, seconds):
    async def untraced(system, mix):
        result = await run.measured(cell, CPU, system, mix, seed, seconds, False)
        ledgers = system.cluster.ledgers
        return result, {
            "views": [int(r.metrics.current_view) for r in system.cluster.replicas],
            "chains": [compare.ledger_payloads(lg) for lg in ledgers],
            "digests": [lg.state_digest() for lg in ledgers]}

    return untraced


def a_control(name, seed, seconds, **override):
    async def controlled(system, mix):
        mix = Mix.from_file(dataclasses.asdict(mix), override) if override else mix
        return (await controls.windows(system, mix, [(name, seed)], seconds, lambda line: None))[0]

    return controlled


CRASH = {"primary": {"faults": [{"at_s": CRASH_AT_S, "kind": "crash", "target": "primary"}]},
         "backup": {"faults": [{"at_s": CRASH_AT_S, "kind": "crash", "target": "backup"}]}}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return faulty_checkout(tmp_path_factory.mktemp("faults"), {
        "closed-crash-primary": CRASH["primary"], "closed-crash-backup": CRASH["backup"],
        "open-20": {"loop": "open", "rate_rps": 20.0}})


@pytest.fixture(scope="module")
def primary_crashed(tree):
    cell = tree["closed-crash-primary"]
    (result, seen), diverged = drive(cell, [
        a_run(cell, SEED, 8.0), a_control("crashed_diverges", SEED + 1, 1.0, faults=[])], 240)
    return result, seen, diverged


@pytest.fixture(scope="module")
def backup_crashed(tree):
    cell = tree["closed-crash-backup"]
    (result, seen), later = drive(cell, [
        a_run(cell, SEED + 2, 4.0), a_control("sound", SEED + 3, 1.0, faults=[])], 240)
    return result, seen, later


@pytest.fixture(scope="module")
def open_loop_then_unexplained(tree):
    cell = tree["open-20"]
    (result, seen), unexplained = drive(cell, [
        a_run(cell, SEED + 4, 2.0), a_control("view_unexplained", SEED + 5, 4.0, loop="closed")], 240)
    return result, seen, unexplained


def held(result, seen, crashed: int, view: int):
    """What every sound window with one replica down has to show."""
    assert result["correct"] is True, json.dumps(result["compared"])
    assert not any(line["value"] for line in result["compared"].values())
    assert list(result["compared"]) == list(compare.LIMITS) and result["failed"] == 0
    running = [r for r in range(3) if r != crashed]
    assert [seen["views"][r] for r in running] == [view, view]
    order = seen["chains"][running[0]]
    assert seen["chains"][running[1]] == order and len(set(order)) == len(order)
    assert [seen["digests"][r] for r in running] == [compare.replay(order)[0]] * 2
    gone = seen["chains"][crashed]
    assert 0 < len(gone) < len(order) and gone == order[:len(gone)]
    assert seen["digests"][crashed] == compare.replay(gone)[0]
    notes = result["notes"]
    (fault,) = notes["faults_applied"]
    assert fault["kind"] == "crash" and fault["replica"] == crashed
    assert CRASH_AT_S <= fault["at_s"] < CRASH_AT_S + 0.5
    assert len(notes["first_ack_after_fault_ms"]) == 1 and notes["first_ack_after_fault_ms"][0] > 0
    return notes


def test_the_primary_crashed_under_load_every_write_answered_in_view_1(primary_crashed):
    result, seen, _ = primary_crashed
    notes = held(result, seen, crashed=0, view=1)
    assert result["attempted"] > 8
    # what the backups had prepared at the crash they commit between themselves
    # (so the first ack after it can come at once); then nothing is acknowledged
    # until their request timers have run out and the view has changed, and
    # nothing waits beyond ack_wait_s
    assert TIMERS["timeout_request"] * 1e3 <= notes["longest_ack_gap_ms"] < 60e3
    assert set(result["metrics"]) == {"goodput_rps", "finality_mean_ms", "finality_p95_ms", "setup_s"}


def test_a_backup_crashed_under_load_nothing_stops_and_the_view_stays(backup_crashed):
    result, seen, later = backup_crashed
    notes = held(result, seen, crashed=2, view=0)
    assert notes["longest_ack_gap_ms"] < TIMERS["timeout_request"] * 1e3
    # it stays down: the next window of that process is judged with it down, and passes
    assert later["correct"] is True and not any(later["numbers"].values()), later


def test_a_crashed_ledger_with_a_block_of_its_own_fails_agreement(primary_crashed):
    line = primary_crashed[2]
    assert line["correct"] is False and line["attempted"] > 0
    assert line["numbers"]["ledgers_off_reference"] == 1
    assert not any(v for k, v in line["numbers"].items() if k != "ledgers_off_reference"), line


def test_a_view_change_that_no_scheduled_fault_explains_fails_view_changes_alone(open_loop_then_unexplained):
    line = open_loop_then_unexplained[2]
    assert line["correct"] is False and line["attempted"] > 0
    assert line["numbers"]["view_changes"] == 2  # both running replicas, in view 1
    assert not any(v for k, v in line["numbers"].items() if k != "view_changes"), line


def test_an_open_loop_says_how_late_its_generator_ran(open_loop_then_unexplained):
    result, _seen, _ = open_loop_then_unexplained
    assert result["correct"] is True, json.dumps(result["compared"])
    notes = result["notes"]
    assert 20 <= notes["arrivals"] <= 70 and notes["arrivals"] == result["attempted"]
    assert 0 <= notes["generator_late_p50_ms"] <= notes["generator_late_max_ms"] < 2000
    assert "faults_applied" not in notes and "longest_ack_gap_ms" not in notes


def test_a_closed_loop_without_faults_adds_no_note(backup_crashed, primary_crashed):
    for notes in (backup_crashed[0]["notes"], primary_crashed[0]["notes"]):
        assert "arrivals" not in notes and "generator_late_p50_ms" not in notes


def test_the_controls_give_a_cell_with_a_schedule_a_cluster_for_every_window(tree, monkeypatch, capsys):
    """A crashed replica stays down and the guarantees cover f of them, so
    the schedule can run once a cluster: a sound window, then on a second
    cluster ``crashed_diverges`` around the scheduled crash itself."""
    assert [name for name, _ in controls.plan_for(1, sound=1, each=1)] == ["sound", *controls.SABOTAGES]
    assert [name for name, _ in controls.plan_for(1, sound=0, each=1, with_faults=True)] == [
        *controls.SABOTAGES, *controls.WITH_FAULTS]
    built, plans = [], []
    build = sut.build

    async def counted(*args, **kwargs):
        built.append(await build(*args, **kwargs))
        return built[-1]

    def short_plan(first_seed, **kwargs):
        plans.append(kwargs)
        return [("sound", first_seed), ("crashed_diverges", first_seed + 1)]

    monkeypatch.setattr(sut, "build", counted)
    monkeypatch.setattr(controls, "plan_for", short_plan)
    logging.disable(logging.WARNING)
    try:
        with timeline_from_here():
            rc = asyncio.run(asyncio.wait_for(
                controls._main(tree["closed-crash-backup"], CPU, SEED + 6, 3.0), 240))
    finally:
        logging.disable(logging.NOTSET)
    sound, diverged, summary = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert plans == [{"with_faults": True}] and len(built) == 2 and built[0] is not built[1]
    assert [len(system.faults_applied) for system in built] == [1, 1]
    assert sound["correct"] is True and not any(sound["numbers"].values()), sound
    assert diverged["correct"] is False and diverged["numbers"]["ledgers_off_reference"] == 1
    assert not any(v for k, v in diverged["numbers"].items() if k != "ledgers_off_reference"), diverged
    assert rc == 0 and summary["windows"] == 2 and summary["unexpected"] == []


def test_the_third_timer_reaches_the_cluster_when_the_file_states_it(monkeypatch):
    from minbft_tpu.sample import config as sample_config

    made = []

    class Stop(Exception):
        pass

    def configer(**kw):
        made.append(kw)
        raise Stop

    monkeypatch.setattr(sample_config, "SimpleConfiger", configer)
    for config in (ACCEPTED.config, {**ACCEPTED.config, **TIMERS}):
        with pytest.raises(Stop):
            asyncio.run(sut.build(ACCEPTED, config, 1, on_cpu=True))
    assert made[0] == {"n": 3, "f": 1, "timeout_request": 60.0, "timeout_prepare": 30.0}
    assert made[1] == {"n": 3, "f": 1, **TIMERS}


def test_the_crash_kind_takes_the_stub_and_the_replica_down_and_nothing_else():
    calls = []
    stub = types.SimpleNamespace(crash=lambda: calls.append("stub"))

    async def stop():
        calls.append("replica")

    system = types.SimpleNamespace(cluster=types.SimpleNamespace(
        stubs=[None, stub], replicas=[None, types.SimpleNamespace(stop=stop)], engines=[None, None]))
    crash = manifest.by_name(REPO, "faults", "crash", "fault kind")
    asyncio.run(crash.apply(system, 1))
    assert calls == ["stub", "replica"]
