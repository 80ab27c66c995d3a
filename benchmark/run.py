"""One run of one cell:

    python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (JAX and the chip, the compile cache, the cluster with every engine
warmed, keys, clients, one committed write), then a measured window of
``--seconds`` in which nothing compiles, then the comparison that decides
``correct`` and, with ``--trace 1``, the profiler's calibration sessions.
The last line of standard output is the result.  Without a chip the run
exits non-zero and prints no result; ``JAX_PLATFORMS=cpu`` set by hand makes
it a rehearsal at a tiny size that names ``cpu`` as its device and is never
a reading.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up counts from here: before JAX is imported

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

from . import compare as cmp  # noqa: E402
from . import manifest, observe, spans, tracing  # noqa: E402
from .manifest import BenchmarkError  # noqa: E402
from .generator import Mix, Window  # noqa: E402

UNATTRIBUTED = (
    "all idle time of the window together: the program's timeline does not "
    "hold the whole window (a ring lost rows), so it is not split by class"
)


def start_jax(chips: int) -> dict:
    """JAX on the cell's chips, or BenchmarkError.  -> the device as JAX
    reports it, plus ``rehearsal`` (the CPU backend, asked for by hand)."""
    by_hand = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update(
            "jax_compilation_cache_dir", os.path.join(manifest.ROOT, ".jax_cache")
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu" and not by_hand:
        raise BenchmarkError(
            "JAX found no accelerator (it runs on the cpu backend): no result"
        )
    if platform != "cpu" and len(devices) < chips:
        raise BenchmarkError(f"the cell asks for {chips} chips, JAX has {len(devices)}")
    return {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices), "rehearsal": platform == "cpu",
    }


def memory_peak_bytes() -> int:
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in jax.local_devices()
    ]
    return int(max(peaks))


async def one_window(system, mix: Mix, seed: int, seconds: float,
                     tag: bytes = b"w") -> dict:
    """Drive one window over a built system and compare what it produced.
    -> everything a result line, or a control's line, is made from."""
    queues = system.queues
    window = Window(system, mix, seed, seconds, tag)
    loop = asyncio.get_running_loop()

    def counts() -> list:
        return [cmp.engine_counts(e, queues) for e in system.engines]

    def since(then: list, now: list) -> list:
        return [cmp.counts_delta(a, b) for a, b in zip(now, then)]

    before = counts()
    at_close: list = []
    handle = loop.call_later(seconds, lambda: at_close.append(counts()))
    await window.run()
    handle.cancel()
    deltas = since(before, at_close[0] if at_close else counts())
    peak = memory_peak_bytes()
    await cmp.converged([system.cluster.ledgers[r] for r in cmp.running(system)])
    system.requested.update(r.op for r in window.issued)
    now = counts()  # the tail past the close included: did every engine work?
    numbers = cmp.compare(
        system, window.issued, set(window.forged_ops), since(before, now), now
    )
    system.forged_sent.update(window.forged_ops)
    answered = [r for r in window.issued if r.acked is not None]
    return {
        "window": window,
        "numbers": numbers,
        "latencies_ms": sorted((r.acked - r.due) * 1e3 for r in answered),
        "commits": sum(r.acked <= window.closed for r in answered),
        "deltas": deltas,
        "memory_peak_bytes": peak,
    }


def longest_ack_gap_ms(window) -> Optional[float]:
    """The longest stretch that began inside the window with no write
    acknowledged, in a window in which a scheduled fault was applied (None
    in any other): what a failure cost the callers, a view change and the
    timers that ran out before it included."""
    if not window.faults_applied:
        return None
    acks = [r.acked for r in window.issued if r.acked is not None]
    marks = sorted([window.opened, window.closed] + acks)
    return max(b - a for a, b in zip(marks, marks[1:]) if a < window.closed) * 1e3


def what_the_window_says(window) -> dict:
    """Notes, not metrics: how late an open loop's generator ran, and for a
    window with faults which were applied and how long their callers waited
    (``longest_ack_gap_ms`` is the end-to-end metric ``outage_ms`` of a cell
    that lists it)."""
    notes: dict = {}
    if window.mix.loop == "open":
        late = sorted(window.generator_late_s)
        notes.update(arrivals=len(late),
                     generator_late_p50_ms=observe.percentile(late, 50) * 1e3 if late else None,
                     generator_late_max_ms=late[-1] * 1e3 if late else None)
    if window.faults_applied:
        acks = sorted(r.acked for r in window.issued if r.acked is not None)
        notes.update(
            faults_applied=[
                {"kind": a.kind, "replica": a.replica, "at_s": a.at - window.opened}
                for a in window.faults_applied],
            longest_ack_gap_ms=longest_ack_gap_ms(window),
            first_ack_after_fault_ms=[
                next(((t - a.at) * 1e3 for t in acks if t > a.at), None)
                for a in window.faults_applied])
    return notes


def end_to_end(cell, got: dict, seconds: float, setup_s: float) -> dict:
    lat = got["latencies_ms"]
    values = {
        "goodput_rps": got["commits"] / seconds,
        "finality_mean_ms": sum(lat) / len(lat) if lat else None,
        "finality_p95_ms": observe.percentile(lat, 95) if lat else None,
        "setup_s": setup_s,
        "outage_ms": longest_ack_gap_ms(got["window"]),
    }
    out = {}
    for m in cell.end_to_end:
        if m["name"] not in values:
            raise BenchmarkError(f"benchmark/run.py takes no end-to-end metric {m['name']!r}")
        if values[m["name"]] is not None:
            out[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    return out


def per_layer(cell, obs: observe.Observations) -> dict:
    out = {}
    for m in cell.per_layer:
        value = m.read(obs)
        if value is not None:
            out[m.name] = {"value": value, "unit": m.unit}
    return out


def idle_gaps(obs: observe.Observations) -> list:
    """The window's idle device time by what the host was doing
    (benchmark/spans.py's classes, the same as the ``device.idle_<class>_share``
    metrics'), ``[["idle_<class>", seconds], ...]`` largest first; they sum
    to the window less the modelled kernels' time.  One unattributed row,
    the window less ``busy_s``, where the timeline cannot be split."""
    a = spans.analysis(obs)
    if a is None or a.classes is None:
        return [[UNATTRIBUTED, max(obs.window_s - obs.busy_s, 0.0)]]
    return sorted(([f"idle_{c}", a.classes[c] / 1e9] for c in spans.CLASSES),
                  key=lambda row: -row[1])


def sized(cell, device: dict) -> tuple:
    """-> (configuration, mix) as this run builds them: the cell's own, or
    on the CPU backend of a rehearsal benchmark/rehearsal.json's tiny size."""
    config, mix_data = dict(cell.config), dict(cell.traffic)
    if device["rehearsal"]:
        rehearsal = manifest.read_json(os.path.join(manifest.HERE, "rehearsal.json"))
        config["engine"] = rehearsal["engine"]
        mix_data.update({k: rehearsal[k] for k in mix_data if k in rehearsal})
    mix = Mix.from_file(mix_data)
    mix.against(config, cell.root)  # a schedule that cannot be run or judged ends here
    return config, mix


async def measure(cell, device: dict, seed: int, seconds: float, trace: bool) -> dict:
    from . import system as sut

    config, mix = sized(cell, device)
    system = await sut.build(cell, config, mix.clients, on_cpu=device["rehearsal"])
    try:
        return await measured(cell, device, system, mix, seed, seconds, trace)
    finally:
        await system.stop()


async def measured(cell, device: dict, system, mix: Mix, seed: int, seconds: float,
                   trace: bool) -> dict:
    """The run from the end of set-up on: the window, the comparison, the
    traced sessions, the result."""
    config, kernels = system.config, system.kernels
    setup_s = time.perf_counter() - _T0
    got = await one_window(system, mix, seed, seconds)
    result = {
        "correct": cmp.verdict(got["numbers"]),
        "attempted": len(got["window"].issued),
        "failed": got["numbers"]["never_answered"] + got["numbers"]["wrong_results"],
    }
    dev = {k: device[k] for k in ("platform", "kind", "count")}
    dev["memory_peak_bytes"] = got["memory_peak_bytes"]
    notes = {"engines_warm_s": system.engines_warm_s,
             "forged_requests": len(got["window"].forged_ops),
             "shadowed_writes": sum(r.shadowed for r in got["window"].issued),
             **what_the_window_says(got["window"])}
    if not trace:
        result["metrics"] = end_to_end(cell, got, seconds, setup_s)
    else:
        await tracing.quiet(system)
        cal = await tracing.calibrate(
            tracing.HostClockProfiler() if device["rehearsal"] else tracing.Profiler(),
            tracing.Dispatcher(system.engines[0], kernels), kernels,
            deadline=tracing.deadline(_T0, setup_s),
            anchors=tracing.anchors(kernels, config["engine"]["buckets"][0], system.root),
        )
        dispatches = {  # each kernel's from its own queue's counter
            k: sum(d["batches"][m.QUEUE, m.KIND] for d in got["deltas"])
            for k, m in kernels.items()
        }
        busy = {k: dispatches[k] * cal["kernel_time_s"][k] for k in kernels}
        obs = observe.Observations(
            window_s=seconds, latencies_ms=got["latencies_ms"],
            commits=got["commits"], engine_deltas=got["deltas"],
            device_kind=device["kind"],
            platform=device["platform"], kernels=kernels,
            kernel_time_s=cal["kernel_time_s"], kernel_dispatches=dispatches,
            lanes=config["engine"]["buckets"][0], busy_s=sum(busy.values()),
        )
        result["metrics"] = per_layer(cell, obs)
        dev["busy_s"] = obs.busy_s
        dev["window_s"] = seconds
        result["breakdown"] = {
            "device_ops": sorted(
                ([kernels[k].TRACE_NAME, s] for k, s in busy.items()),
                key=lambda kv: -kv[1],
            )[:10],
            "idle_gaps": idle_gaps(obs),
        }
        notes.update(trace_sessions=cal["sessions"], kernel_dispatches=dispatches,
                     end_to_end_of_this_traced_window=end_to_end(
                         cell, got, seconds, setup_s))
    result["device"] = dev
    result["workload"] = cell.name
    result["seed"] = seed
    result["notes"] = notes
    result["compared"] = cmp.compared_lines(got["numbers"])
    return result


def main(argv: Optional[list] = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    # The replicas log every request they reject, and the traffic carries
    # forged ones: keep standard error for the numbers compared.
    logging.disable(logging.WARNING)
    try:
        cell = manifest.load_cell(args.workload)
        device = start_jax(cell.chips)
        result = asyncio.run(
            measure(cell, device, args.seed, args.seconds, bool(args.trace))
        )
    except BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1
    for name, line in result["compared"].items():
        print(f"compared {name}: {line['value']} (limit {line['limit']})", file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
