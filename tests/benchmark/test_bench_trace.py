"""The reduction from a profiler session to the kernels' device time, on
recorded sessions, and the retry loop around the profiler, on a stub."""

import asyncio
import io
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, tracing  # noqa: E402

KERNELS = manifest.load_kernels(manifest.load_cell("n3f1-ecdsa.closed-16x8"))


def recorded(name: str) -> dict:
    with open(os.path.join(manifest.HERE, "recorded", name + ".json")) as fh:
        return json.load(fh)


# The recorded sessions' dispatches by the host's clock were not kept: a
# 512-lane verify dispatch takes ~11 ms to np.asarray (PERF.md, PR 21), a sign ~13.
RUNS = [("jit__verify_one_packed", 0.0, 0.0113), ("jit__kg_comb_widen", 0.1, 0.1136),
        ("jit__kg_comb_widen", 0.2, 0.2136)]


def reduce(session: dict, runs=RUNS) -> dict:
    return tracing.reduce_calibration(session, tracing.TPU_EVENTS, KERNELS, runs)


def test_recorded_session_reduces_to_the_kernels_device_time():
    times = reduce(recorded("calibration_session"))
    assert times == {"ecdsa_verify": pytest.approx(9.5542295e-3),  # median of its two events
                     "ecdsa_sign": pytest.approx(1.128426e-3)}
    found = tracing.kernel_events(recorded("calibration_session"), tracing.TPU_EVENTS, KERNELS)
    assert len(found["ecdsa_verify"]) == 2 and len(found["ecdsa_sign"]) == 3


def test_one_whole_verify_event_is_enough_and_none_is_not():
    # what the profiler's buffer holds of two verify dispatches, as recorded
    assert reduce(recorded("second_verify_dropped"))["ecdsa_verify"] == pytest.approx(9.557247e-3)
    session = recorded("second_verify_dropped")
    for plane in session["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"] if not e[0].startswith("jit__verify")]
    with pytest.raises(tracing.TraceError, match="ecdsa_verify: 0 whole event"):
        reduce(session)


@pytest.mark.parametrize("name,why", [
    ("half_caught_pr24", "kernel ecdsa_verify: 0 whole event"),
    ("no_device_plane_pr24", "no plane matches"),
])
def test_sessions_that_refused_pr24_are_thrown_away(name, why):
    with pytest.raises(tracing.TraceError, match=why):
        reduce(recorded(name))


def test_a_kernel_event_cut_short_is_thrown_away():
    session = recorded("calibration_session")
    for plane in session["planes"]:
        for line in plane["lines"]:
            for event in line["events"]:
                if event[0].startswith("jit__verify_one_packed"):
                    event[2] = event[2] // 2  # caught from its middle on
                    break
    with pytest.raises(tracing.TraceError, match="disagree"):
        reduce(session)


def test_an_event_longer_than_its_dispatch_or_a_sliver_of_it_does_not_count():
    with pytest.raises(tracing.TraceError, match="2 longer than a dispatch"):
        reduce(recorded("calibration_session"), [("jit__verify_one_packed", 0.0, 0.005)] + RUNS[1:])
    with pytest.raises(tracing.TraceError, match="a lone event"):
        reduce(recorded("second_verify_dropped"), [("jit__verify_one_packed", 0.0, 0.5)] + RUNS[1:])
    # the cold run's session: a whole event beside one of four times its length
    session = recorded("second_verify_dropped")
    for plane in session["planes"]:
        for line in plane["lines"]:
            line["events"] += [[e[0], e[1] + 5e7, 38219000.0] for e in line["events"]
                               if e[0].startswith("jit__verify")]
    assert reduce(session)["ecdsa_verify"] == pytest.approx(9.557247e-3)


def test_a_run_that_compiled_has_time_for_more_sessions():
    assert tracing.deadline(100.0, 75.0) == 100.0 + 270.0
    assert tracing.deadline(100.0, 148.0) == 100.0 + 1110.0


class StubProfiler:
    """Fails ``failures`` sessions, each in another way, then returns the
    recorded session."""

    where = tracing.TPU_EVENTS

    def __init__(self, failures: int):
        self.failures = failures
        self.started = self.stopped = self.abandoned = 0

    def start(self):
        self.started += 1
        if self.started <= self.failures and self.started % 4 == 1:
            raise RuntimeError("Only one profile may be run at a time.")

    def stop(self, runs):
        self.stopped += 1
        if self.started <= self.failures:
            kind = self.started % 4
            if kind == 2:
                raise RuntimeError("No profile started")
            return recorded("half_caught_pr24" if kind == 3 else "no_device_plane_pr24")
        return recorded("calibration_session")

    def abandon(self):
        self.abandoned += 1


class StubDispatcher:
    def __init__(self):
        self.ran = []

    async def run(self, kernel):
        self.ran.append(kernel)
        await asyncio.sleep(0.012)  # a dispatch lasts longer than its device event


def test_four_unusable_sessions_then_a_good_one_still_give_the_times():
    profiler, dispatcher, log = StubProfiler(4), StubDispatcher(), io.StringIO()
    cal = asyncio.run(tracing.calibrate(profiler, dispatcher, KERNELS, log=log))
    assert set(cal["kernel_time_s"]) == set(KERNELS)
    assert [("error" in s) for s in cal["sessions"]] == [True] * 4 + [False]
    assert profiler.abandoned == 4 and log.getvalue().count("unusable") == 4
    # an arming sign run, then each kernel as often as its file says
    assert dispatcher.ran[-4:] == ["ecdsa_sign", "ecdsa_verify", "ecdsa_sign", "ecdsa_sign"]


def test_five_unusable_sessions_in_a_row_end_the_run():
    profiler, log = StubProfiler(5), io.StringIO()
    with pytest.raises(manifest.BenchmarkError, match="5 profiler sessions in a row"):
        asyncio.run(tracing.calibrate(profiler, StubDispatcher(), KERNELS, log=log))
    assert profiler.started == 5 and log.getvalue().count("unusable") == 5


def test_no_session_starts_past_the_deadline():
    profiler, log = StubProfiler(5), io.StringIO()
    with pytest.raises(manifest.BenchmarkError, match="1 profiler sessions in a row"):
        asyncio.run(tracing.calibrate(profiler, StubDispatcher(), KERNELS, log=log,
                                      deadline=0.0))
    assert profiler.started == 1
