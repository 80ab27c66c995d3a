"""The rest of a run with the look for a chip skipped: a tiny cluster on the
CPU backend (loop lowering, 8-lane bucket, n=3 f=1, 2 clients x 2 in
flight) driven through the run's own window, comparison and result line;
then the same cluster with the timed path broken underneath, once for each
control, where ``correct`` has to come out false."""

import asyncio
import json
import logging
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import compare, controls, manifest, run, spans  # noqa: E402
from benchmark import system as sut  # noqa: E402
from bench_timeline import timeline_from_here  # noqa: E402  (this directory)

CELL = "n3f1-ecdsa.closed-16x8"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "rehearsal": True}
SEED = 2**31 + 2025
# What each control has to trip (it may trip more).
TRIPS = {
    "replies_unverified": "wrong_results",
    "acks_on_f": "acks_short_of_quorum",
    "answer_altered": "wrong_results",
    "state_unchanged": "wrong_results",
    "verify_skipped": "forged_executed",
}


@pytest.fixture(scope="module")
def rehearsed():
    """-> (a traced run's result, {(step, seed): control line})."""
    cell = manifest.load_cell(CELL)

    async def everything():
        config, mix = run.sized(cell, CPU)
        system = await sut.build(cell, config, mix.clients, on_cpu=True)
        try:
            traced = await run.measured(cell, CPU, system, mix, SEED, 1.0, True)
            # verify_skipped comes last: replicas that skip verification
            # stop agreeing, and no later window on that cluster is sound.
            assert controls.SABOTAGES[-1] == "verify_skipped"
            plan = [("sound", SEED + 2)] + [
                (name, SEED + 3 + k) for k, name in enumerate(controls.SABOTAGES)
            ]
            plan.insert(-1, ("sound", SEED + 9))
            lines = await controls.windows(system, mix, plan, 1.0, lambda line: None)
        finally:
            await system.stop()
        return traced, lines

    logging.disable(logging.WARNING)
    try:
        with timeline_from_here():
            traced, lines = asyncio.run(everything())
    finally:
        logging.disable(logging.NOTSET)
    return traced, {(ln["step"], ln["seed"]): ln for ln in lines}


def test_run_is_correct_and_takes_the_end_to_end_metrics(rehearsed):
    traced, _ = rehearsed
    assert traced["correct"] is True and traced["failed"] == 0 and traced["attempted"] > 0
    end_to_end = traced["notes"]["end_to_end_of_this_traced_window"]
    assert set(end_to_end) == {"goodput_rps", "finality_mean_ms", "finality_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in end_to_end.values())
    assert traced["device"]["platform"] == "cpu"  # a rehearsal says so, always
    assert list(traced)[-1] == "compared" and set(traced["compared"]) == set(compare.LIMITS)
    assert traced["notes"]["forged_requests"] > 0 and traced["notes"]["shadowed_writes"] > 0
    json.dumps(traced)


def test_traced_run_reports_the_per_layer_metrics_and_the_device_times(rehearsed):
    traced, _ = rehearsed
    assert traced["correct"] is True
    # every per-layer metric the manifest gives the cell; on the CPU backend
    # there are no peaks, so the shares of one are left out, not 0
    cell = manifest.load_cell(CELL)
    assert set(traced["metrics"]) == {
        m.name for m in cell.per_layer if not (m.unit == "%" and m.source == "device_trace")}
    assert all(traced["metrics"][m.name]["unit"] == m.unit
               for m in cell.per_layer if m.name in traced["metrics"])
    assert traced["device"]["busy_s"] > 0 and traced["device"]["window_s"] == 1.0
    assert len(traced["breakdown"]["device_ops"]) == len(cell.config["kernels"])
    assert traced["notes"]["trace_sessions"][-1]["kernel_time_s"]["ecdsa_verify"] > 0


def test_traced_run_breaks_the_idle_time_down_by_what_the_host_was_doing(rehearsed):
    """``breakdown.idle_gaps``: the five classes of the ``device.idle_*_share``
    metrics, in seconds, largest first, where the timeline was whole."""
    traced, _ = rehearsed
    gaps = traced["breakdown"]["idle_gaps"]
    assert sorted(name for name, _s in gaps) == sorted(f"idle_{c}" for c in spans.CLASSES)
    assert [s for _n, s in gaps] == sorted((s for _n, s in gaps), reverse=True)
    for name, seconds in gaps:
        share = traced["metrics"][f"device.{name}_share"]["value"]
        assert seconds == pytest.approx(share * traced["device"]["window_s"], abs=1e-9)


@pytest.mark.parametrize("seed", [SEED + 2, SEED + 9], ids=["first", "after_the_controls"])
def test_sound_windows_pass_before_and_after_the_controls(rehearsed, seed):
    line = rehearsed[1][("sound", seed)]
    assert line["correct"] is True and not any(line["numbers"].values()), json.dumps(line)


@pytest.mark.parametrize("k,name", list(enumerate(controls.SABOTAGES)),
                         ids=list(controls.SABOTAGES))
def test_control_comes_out_not_correct(rehearsed, k, name):
    line = rehearsed[1][(name, SEED + 3 + k)]
    assert line["correct"] is False
    assert line["numbers"][TRIPS[name]] > 0, line
    assert line["attempted"] > 0


def test_no_chip_means_no_result(monkeypatch, capsys):
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)  # not asked for by hand
    with pytest.raises(manifest.BenchmarkError, match="no accelerator"):
        run.start_jax(1)
    assert run.main(["--workload", CELL, "--seed", "1", "--seconds", "1"]) == 1
    assert capsys.readouterr().out == ""
