"""Device time from the profiler: sessions, their reduction, the retries.

The block-lowered ECDSA kernels are one ``while`` loop of small vector
operations, and the TPU profiler records every one: some 300,000 events per
512-lane verify dispatch, tens of seconds of ``stop_trace`` each.  A slice
of the load cannot be traced (PERF.md, Findings).  So a traced run takes
the kernels' device time from short calibration sessions after the window,
when the cluster is quiet: a few dispatches through the engines the window
used, at the same bucket, each awaited.  ``busy_s`` is that time multiplied
by the dispatches the engines counted in the window.

The profiler's device buffer holds about one verify dispatch: of two in one
session the second left no module event in 8 sessions of 14, and the first
session of a process that meets a verify dispatch lost it in 9 of 13
(PERF.md, Findings, PR 25).  So each kernel's file says how many of its
dispatches a session makes (``CALIBRATION_RUNS``: verify 1, sign 2), and a
traced run expects to make two sessions.  An event longer than any of the
session's dispatches took by the host's clock is none of them and is left
out.  A session is thrown away unless it then holds that many whole module
events of every kernel, all of one kernel agreeing.  A lone event has no
other to agree with: it is held to the kernel's own time where the chip has
recorded one at this bucket (``benchmark/recorded/anchors/<kernel>.<lanes>.json``,
within ``AGREE``: a kernel's time is a constant of its executable, the
host's is not), and else, or where it disagrees (a later PR's faster
kernel), to ``FLOOR`` of its dispatch's host time.  Up to ``SESSIONS`` are
made, while one more fits into the run's time (:func:`deadline`).  No
profiler call can end the run before the retries are spent.
"""

from __future__ import annotations

import asyncio
import glob
import os
import re
import shutil
import statistics
import sys
import tempfile
import time
from typing import Dict, List, Optional

from .compare import engine_counts
from .manifest import ROOT, BenchmarkError, read_json

SESSIONS = 5  # unusable sessions in a row that end the run
AGREE = 1.25  # a session's longest event of a kernel over its shortest, at most
FLOOR = 0.2  # a lone whole event is at least this share of its dispatch's host time
# A run has 360 s in all, the first of a cell in a checkout (it compiles) 1200 s.
WARM_SETUP_S, WARM_RUN_S, COLD_RUN_S, SESSION_S = 110.0, 360.0, 1200.0, 90.0  # warm set-ups read 71-83 s, cold ones 148 s and more
QUIET_S = 0.5  # no engine dispatches for this long before a session starts

# Where a kernel's whole-dispatch events are in a trace.
TPU_EVENTS = {"plane": r"^/device:TPU:\d+$", "line": r"^XLA Modules$", "event": "{jit}"}
HOST_CLOCK_EVENTS = {"plane": r"^/host:clock$", "line": r"^dispatches$", "event": "{jit}"}


class TraceError(Exception):
    """A profiler session that cannot be used."""


def summarize_xplane(path: str, where: dict) -> dict:
    """The lines and events the reduction reads, as plain JSON: for every
    plane its name, for every line that ``where`` names its events as
    [name, start_ns, duration_ns].  (The recorded trace under
    benchmark/recorded/ is one of these.)"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    plane_re, line_re = re.compile(where["plane"]), re.compile(where["line"])
    planes = []
    for plane in data.planes:
        lines = []
        if plane_re.search(plane.name):
            for line in plane.lines:
                if line_re.search(line.name):
                    lines.append({
                        "name": line.name,
                        "events": [[e.name, e.start_ns, e.duration_ns] for e in line.events],
                    })
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def kernel_events(summary: dict, where: dict, kernels: Dict[str, object]) -> Dict[str, List[float]]:
    """-> {kernel: seconds of each of its whole-dispatch events}."""
    plane_re = re.compile(where["plane"])
    planes = [p for p in summary["planes"] if plane_re.search(p["name"])]
    if not planes:
        raise TraceError(
            f"no plane matches {where['plane']!r} among "
            f"{[p['name'] for p in summary['planes']]}"
        )
    found: Dict[str, List[float]] = {k: [] for k in kernels}
    for name, module in kernels.items():
        jit = module.TRACE_NAME
        prefix = where["event"].format(jit=jit)
        for plane in planes:
            for line in plane["lines"]:
                found[name] += [
                    dur * 1e-9 for ev, _start, dur in line["events"]
                    if ev.startswith(prefix) and dur > 0
                ]
    return found


def anchors(kernels: Dict[str, object], lanes: int, root: str = ROOT) -> Dict[str, float]:
    """-> {kernel: the device seconds of one dispatch at ``lanes`` that the
    chip has recorded}, for the kernels that have a file
    ``<root>/benchmark/recorded/anchors/<kernel>.<lanes>.json``."""
    out = {}
    for name in kernels:
        path = os.path.join(root, "benchmark", "recorded", "anchors", f"{name}.{lanes}.json")
        if os.path.isfile(path):
            out[name] = float(read_json(path)["seconds"])
    return out


def reduce_calibration(summary: dict, where: dict, kernels: Dict[str, object],
                       runs: List[tuple],
                       anchors: Optional[Dict[str, float]] = None) -> Dict[str, float]:
    """One session -> {kernel: device seconds of one dispatch}, the median
    of its whole events.  ``runs`` is the session's dispatches by the
    host's clock: (kernel's trace name, start, end).  A kernel caught
    part-way leaves no module event, or a short one: such a session raises
    :class:`TraceError`.  ``anchors`` (:func:`anchors`) is what a lone
    event is held to first."""
    found = kernel_events(summary, where, kernels)
    out = {}
    for name, traced in found.items():
        module = kernels[name]
        host = [t1 - t0 for jit, t0, t1 in runs if jit == module.TRACE_NAME]
        # An event longer than any of the session's dispatches took by the
        # host's clock is none of them (one cold run's session held a verify
        # event of 4.0003 times the kernel's time beside a whole one).
        seconds = [s for s in traced if not host or s <= max(host)]
        if len(seconds) < module.CALIBRATION_RUNS:
            raise TraceError(
                f"kernel {name}: {len(seconds)} whole event(s) traced, "
                f"{module.CALIBRATION_RUNS} needed of {len(host)} dispatched"
                + (f" ({len(traced) - len(seconds)} longer than a dispatch)"
                   if len(traced) > len(seconds) else "")
            )
        if max(seconds) > AGREE * min(seconds):
            raise TraceError(
                f"kernel {name}: events disagree ({min(seconds):.6f} s to "
                f"{max(seconds):.6f} s): one was caught part-way"
            )
        # a lone event has no other to agree with: hold it to the kernel's
        # recorded time, or failing that to a floor under its dispatch's
        anchor = (anchors or {}).get(name)
        whole = anchor is not None and max(anchor, seconds[0]) <= AGREE * min(anchor, seconds[0])
        if host and len(seconds) == 1 and not whole and seconds[0] < FLOOR * min(host):
            raise TraceError(
                f"kernel {name}: a lone event of {seconds[0]:.6f} s against a dispatch "
                f"of {min(host):.6f} s by the host's clock"
            )
        out[name] = statistics.median(seconds)
    return out


class Profiler:
    """jax.profiler behind three calls, so that a test can put a stub here."""

    where = TPU_EVENTS

    def __init__(self):
        self._dir: Optional[str] = None

    def start(self) -> None:
        import jax

        self._dir = tempfile.mkdtemp(prefix="minbft_bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self._dir, profiler_options=options)

    def stop(self, runs: List[tuple]) -> dict:
        """-> the session's summary; the profile is deleted once reduced.
        ``runs`` (kernel's trace name, start, end by the host's clock) is
        for the rehearsal's stand-in alone."""
        import jax

        try:
            jax.profiler.stop_trace()
            files = glob.glob(os.path.join(self._dir, "plugins", "profile", "*", "*.xplane.pb"))
            if not files:
                raise TraceError("the profiler wrote no xplane.pb")
            summary = summarize_xplane(files[0], self.where)
            summary["xplane_bytes"] = os.path.getsize(files[0])
            return summary
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None

    def abandon(self) -> None:
        """After a failure: leave no session open and no file behind."""
        import jax

        try:
            jax.profiler.stop_trace()
        except Exception:  # nothing was started, or it is already stopped
            pass
        if self._dir:
            shutil.rmtree(self._dir, ignore_errors=True)
            self._dir = None


class HostClockProfiler:
    """The rehearsal's stand-in on the CPU backend, where the profiler drowns
    in the loop-lowered kernels' events as it does on the chip and there is
    no device line to read instead: the session's dispatches by the host's
    clock, in a summary's shape.  Its numbers are never readings."""

    where = HOST_CLOCK_EVENTS

    def start(self) -> None:
        pass

    def stop(self, runs: List[tuple]) -> dict:
        events = [[name, int(t0 * 1e9), int((t1 - t0) * 1e9)] for name, t0, t1 in runs]
        return {"planes": [{"name": "/host:clock",
                            "lines": [{"name": "dispatches", "events": events}]}]}

    def abandon(self) -> None:
        pass


class Dispatcher:
    """Runs one kernel once through an engine the window used: the
    kernel's file sends one fresh item through the engine's public entry,
    which pads it to the bucket."""

    def __init__(self, engine, kernels: Dict[str, object]):
        self._engine = engine
        self._kernels = kernels
        self._n = 0

    async def run(self, kernel: str) -> None:
        self._n += 1
        salt = b"benchmark calibration %d %d" % (id(self), self._n)
        await self._kernels[kernel].dispatch_once(self._engine, salt)


async def quiet(system, seconds: float = QUIET_S, timeout: float = 30.0) -> None:
    """Until no engine's batch counters, in any of the configuration's
    device queues, have moved for ``seconds``."""
    queues = system.queues

    def batches():
        return [engine_counts(e, queues)["batches"] for e in system.engines]

    deadline = time.monotonic() + timeout
    last, since = batches(), time.monotonic()
    while time.monotonic() < deadline:
        await asyncio.sleep(0.05)
        now = batches()
        if now != last:
            last, since = now, time.monotonic()
        elif time.monotonic() - since >= seconds:
            return
    raise BenchmarkError("the engines never went quiet after the window")


async def session(profiler, dispatcher, kernels: Dict[str, object]) -> dict:
    """One calibration session -> its summary.  ``stop`` runs on a thread:
    it takes tens of seconds, and the replicas' timers live on this loop."""
    arm = min(kernels, key=lambda k: kernels[k].work(1)["ops"])
    await asyncio.to_thread(profiler.start)
    await asyncio.sleep(0.2)
    await dispatcher.run(arm)  # the cheap one: wakes the device's tracer
    runs = []
    for name, module in kernels.items():
        await asyncio.sleep(0.4)  # let the tracer drain the dispatch before
        for _ in range(module.CALIBRATION_RUNS):
            t0 = time.perf_counter()
            await dispatcher.run(name)
            runs.append((module.TRACE_NAME, t0, time.perf_counter()))
    await asyncio.sleep(0.1)
    t0 = time.perf_counter()
    summary = await asyncio.to_thread(profiler.stop, runs)
    summary["stop_trace_s"] = time.perf_counter() - t0
    summary["runs"] = runs
    return summary


def deadline(process_start: float, setup_s: float) -> float:
    """When (on ``time.perf_counter``) the last session may start: a
    session's length before the end of the time a run has, which is longer
    for a run whose set-up compiled."""
    allowed = WARM_RUN_S if setup_s <= WARM_SETUP_S else COLD_RUN_S
    return process_start + allowed - SESSION_S


async def calibrate(profiler, dispatcher, kernels: Dict[str, object],
                    log=sys.stderr, deadline: Optional[float] = None,
                    anchors: Optional[Dict[str, float]] = None) -> dict:
    """Sessions until one can be used -> {"kernel_time_s": {kernel: s},
    "sessions": [one line per session made]}.  Every failure is written to
    ``log`` when it happens; :data:`SESSIONS` in a row raise, and so does
    ``deadline`` (on ``time.perf_counter``) passing before a session starts."""
    made: List[dict] = []
    for number in range(1, SESSIONS + 1):
        if deadline is not None and made and time.perf_counter() > deadline:
            break
        t0 = time.perf_counter()
        line = {"session": number}
        try:
            summary = await session(profiler, dispatcher, kernels)
            line["stop_trace_s"] = summary.get("stop_trace_s")
            line["xplane_bytes"] = summary.get("xplane_bytes")
            line["planes"] = [p["name"] for p in summary["planes"]]
            line["events"] = [
                ev for p in summary["planes"] for ln in p["lines"] for ev in ln["events"]
            ][:20]
            times = reduce_calibration(summary, profiler.where, kernels, summary["runs"],
                                       anchors)
        except Exception as e:  # whatever the profiler raised: record, retry
            await asyncio.to_thread(profiler.abandon)
            line.update(error=f"{type(e).__name__}: {e}"[:400],
                        seconds=time.perf_counter() - t0)
            made.append(line)
            print(f"trace session {number} unusable: {line['error']}", file=log, flush=True)
            continue
        line.update(kernel_time_s=times, seconds=time.perf_counter() - t0)
        made.append(line)
        return {"kernel_time_s": times, "sessions": made}
    raise BenchmarkError(
        f"{len(made)} profiler sessions in a row were unusable: "
        + "; ".join(str(m.get("error")) for m in made)
    )
