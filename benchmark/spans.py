"""The program's own timeline, reduced to numbers about one window.

``minbft_tpu.obs.trace.timeline()`` gives, on ``time.monotonic_ns`` (on
Linux the clock of the harness's ``time.perf_counter``): one row per engine
dispatch with eight instants, the collector's passes, JAX's trace and
compile events, one row per client request, and the event loop's idle
time per 10 ms slot.  The profiler cannot trace a slice of the load
(PERF.md section 3), but the kernels' times are constants of the executable
and one chip runs one kernel at a time.  So :func:`device_intervals`
places every kernel on the host's clock from the rows and the calibrated
kernel time, and :func:`attribute` puts every
instant of the window in which no modelled kernel runs down to what the
host was doing.

A reader gets an ``Observations`` and nothing else, and that has no window
instants: :func:`window` finds the window among the client rows.  Against a
program without ``timeline()`` (this PR's parent) every reader here returns
None, and so does every reader of a ring that dropped rows inside the
window: a ring too small shows as a missing metric, never as a wrong one.
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from .observe import percentile

CLASSES = ("gc", "dispatch_host", "unflushed", "loop_busy", "loop_idle")
JAXPR_TRACE = "/jax/core/compile/jaxpr_trace_duration"


def timeline() -> Optional[dict]:
    try:
        from minbft_tpu.obs.trace import timeline as program_timeline
    except ImportError:  # a program older than the timeline
        return None
    return program_timeline()


def window(obs, tl: dict) -> Optional[Tuple[int, int]]:
    """-> (opened, closed) in ns.  ``benchmark/system.py::build`` commits
    exactly one write before any window and ``run.py`` drives one window a
    process, so the window opens at the process's second ``start`` row and
    closes ``obs.window_s`` later."""
    client = tl["client"]
    if client["dropped"]:
        return None  # the first rows are gone: which is the second?
    starts = sorted(t for _c, _s, stage, t in client["rows"] if stage == "start")
    if len(starts) < 2:
        return None
    return starts[1], starts[1] + round(obs.window_s * 1e9)


def _whole_since(oldest_written_at: Optional[int], dropped: int, opened: int) -> bool:
    """Does a ring that dropped ``dropped`` rows still hold every row
    written from ``opened`` on?  Its oldest surviving row says from when."""
    return not dropped or (oldest_written_at is not None and oldest_written_at <= opened)


def dispatch_rows(tl: dict, opened: int) -> Optional[List[dict]]:
    """Every engine's rows as dicts, or None where an engine's ring
    dropped rows written inside the window (a row is written at
    ``t_resolved``)."""
    cols = tl["dispatch_columns"]
    out = []
    for ring in tl["dispatch"]:
        rows = [dict(zip(cols, r)) for r in ring["rows"]]
        oldest = rows[0]["t_resolved"] if rows else None
        if not _whole_since(oldest, ring["dropped"], opened):
            return None
        out += rows
    return out


def kernel_ns_by_side(obs) -> Dict[Tuple[str, str], int]:
    """{(queue, kind): the calibrated kernel time of one dispatch, ns}: a
    kernel's file names the engine queue it runs in (``QUEUE``, the key of
    ``engine.stats`` / ``engine.sign_stats``) and on which side (``KIND``)."""
    return {
        (module.QUEUE, module.KIND): round(obs.kernel_time_s[name] * 1e9)
        for name, module in obs.kernels.items()
        if name in obs.kernel_time_s
    }


def side(row: dict) -> Tuple[str, str]:
    """A dispatch row's (queue, kind) as the kernels' files name them.  The
    ring names a queue by its label, which for a sign queue is ``sign_`` +
    the key of ``engine.sign_stats`` (``parallel/engine.py``,
    ``LABEL_PREFIX``); this is the one place that knows."""
    queue, kind = row["queue"], row["kind"]
    return (queue.removeprefix("sign_") if kind == "sign" else queue), kind


def device_intervals(rows: Iterable[dict], kernel_ns: Dict[Tuple[str, str], int],
                     order: str = "result") -> List[Tuple[dict, int, int]]:
    """The model: one chip that runs one kernel at a time, each for the
    calibrated time of its own (queue, kind), as early as it can.  A kernel
    cannot start before its launch began (``t_prep_end``: in an annotated session the device event
    starts with the ``launch`` phase, about a millisecond before the
    jitted call returns, and under load ``t_launch_end`` is stamped later
    still, once the worker has the interpreter lock again).  Whenever the
    device is free it takes, of the dispatches launched by then, the one
    whose result came back first (``order="result"``), or the one launched
    first (``order="launch"``, plain FIFO on ``t_prep_end``).  Neither is
    the device's own order for certain: the real enqueue falls somewhere
    inside the launch call, and ``t_result`` is stamped once the worker has
    the interpreter lock again.  WHEN the device is busy does not depend on
    the order (a server that never idles with work waiting is busy at the
    same instants whatever it picks), so the idle classes do not either;
    which dispatch waits how long does, and the readers use ``"result"``.
    Device dispatches only (no flag set), of the sides that have a kernel.
    -> [(row, start, end)] in device order.  The residual ``t_result -
    end`` is the launch call's return plus the result's way back
    (:func:`residuals` reports it, unclipped).  Result order minimises the
    residuals' lateness, so their negative share under it is a fitted
    figure; the checks that do not lean on it are the share under
    ``"launch"``, :func:`overdrawn` and :func:`latest_intervals`."""
    key = {"result": "t_result", "launch": "t_prep_end"}[order]
    device = sorted(
        (r for r in rows if not r["flags"] and side(r) in kernel_ns),
        key=lambda r: r["t_prep_end"],
    )
    out, launched, free_at, i = [], [], 0, 0
    while i < len(device) or launched:
        if not launched:
            free_at = max(free_at, device[i]["t_prep_end"])
        while i < len(device) and device[i]["t_prep_end"] <= free_at:
            heapq.heappush(launched, (device[i][key], i))
            i += 1
        row = device[heapq.heappop(launched)[1]]
        out.append((row, free_at, free_at + kernel_ns[side(row)]))
        free_at = out[-1][2]
    return out


def overdrawn(intervals: Sequence[Tuple[dict, int, int]]) -> List[int]:
    """The check that no order can bend: by the instant a result is back,
    the kernels of every result back by then have run, so their summed
    time cannot exceed the time the modelled device has been busy by then
    (it is never idle with a launched kernel waiting, so no order is busy
    longer).  -> per dispatch in result order, the excess in ns; a
    positive entry means a kernel ran before its launch, faster than
    calibrated, or beside another."""
    starts = [s for _r, s, _e in intervals]
    ends = [e for _r, _s, e in intervals]
    done = [0]
    for s, e in zip(starts, ends):
        done.append(done[-1] + e - s)
    out, work = [], 0
    for row, s, e in sorted(intervals, key=lambda x: x[0]["t_result"]):
        work += e - s
        at = row["t_result"]
        k = bisect.bisect_right(ends, at)
        busy = done[k] + (max(0, at - starts[k]) if k < len(starts) else 0)
        out.append(work - busy)
    return out


def latest_intervals(intervals: Sequence[Tuple[dict, int, int]],
                     return_ns: int = 0) -> List[Tuple[dict, int, int]]:
    """The other end of what the host's instants allow.  The model places
    every kernel as EARLY as it can have run; here each runs as LATE as it
    can: it ended ``return_ns`` before its result was back at the latest,
    and before the next one (in result order) started.  The truth lies
    between the two, so a class that reads the same under both does not
    hang on where a kernel was placed."""
    out, then = [], None
    for row, s, e in sorted(intervals, key=lambda x: x[0]["t_result"], reverse=True):
        end = row["t_result"] - return_ns
        if then is not None and then < end:
            end = then
        then = end - (e - s)
        out.append((row, then, end))
    return out[::-1]


def residuals(intervals: Sequence[Tuple[dict, int, int]]) -> List[int]:
    return [row["t_result"] - end for row, _start, end in intervals]


def _clipped(intervals: Iterable[Tuple[int, int]], lo: int, hi: int):
    for a, b in intervals:
        a, b = max(a, lo), min(b, hi)
        if a < b:
            yield a, b


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    total, reach = 0, None
    for a, b in sorted(intervals):
        if reach is None or a > reach:
            total += b - a
            reach = b
        elif b > reach:
            total += b - reach
            reach = b
    return total


def idle_ns_between(idle_by_slot: Dict[int, int], slot_ns: int, a: int, b: int) -> int:
    """The loop's idle time in [a, b): known per slot, not inside one, so a
    slot cut by ``a`` or ``b`` gives its idle time in proportion."""
    total = 0
    for slot in range(a // slot_ns, (b - 1) // slot_ns + 1):
        part = min(b, (slot + 1) * slot_ns) - max(a, slot * slot_ns)
        total += min(idle_by_slot.get(slot, 0), slot_ns) * part // slot_ns
    return total


def attribute(opened: int, closed: int, busy: Iterable[Tuple[int, int]],
              gc: Iterable[Tuple[int, int]], dispatch_host: Iterable[Tuple[int, int]],
              unflushed: Iterable[Tuple[int, int]], slot_ns: int,
              idle_by_slot: Dict[int, int]) -> Dict[str, int]:
    """Every instant of [opened, closed) -> ns per class: ``busy`` where a
    modelled kernel runs, else the first that holds of: a collector pass
    covers it (``gc``); some dispatch is between ``t_flush`` and
    ``t_prep_end``, in thread hop or prep (``dispatch_host``: from
    ``t_prep_end`` on its kernel is the device's to start); some batch is between
    ``t_first_enqueue`` and ``t_flush`` (``unflushed``); else the loop's
    own state.  The loop's idle time is known per 10 ms slot, not inside
    one, so what is left of a slot is split by the slot's idle share:
    ``loop_idle`` that share of it, ``loop_busy`` the rest."""
    events = []
    for rank, intervals in enumerate((busy, gc, dispatch_host, unflushed)):
        for a, b in _clipped(intervals, opened, closed):
            events.append((a, rank, 1))
            events.append((b, rank, -1))
    events.append((closed, 4, 0))
    events.sort()
    out = dict.fromkeys(("busy",) + CLASSES, 0)
    names = ("busy", "gc", "dispatch_host", "unflushed")
    cover = [0, 0, 0, 0]
    at = opened
    for t, rank, delta in events:
        if t > at:
            first = next((names[k] for k in range(4) if cover[k]), None)
            if first is not None:
                out[first] += t - at
            else:
                idle = idle_ns_between(idle_by_slot, slot_ns, at, t)
                out["loop_idle"] += idle
                out["loop_busy"] += t - at - idle
            at = t
        if rank < 4:
            cover[rank] += delta
    return out


@dataclasses.dataclass
class Analysis:
    """What the readers share of one window; None where the timeline did
    not hold what it takes."""

    opened: int
    closed: int
    classes: Optional[Dict[str, int]] = None  # "busy" and CLASSES -> ns
    loop_idle_ns: Optional[int] = None
    device_queue_wait_ns: Optional[List[int]] = None  # per device dispatch whose launch began in the window
    loop_wake_ns: Optional[List[int]] = None
    result_return_ns: Optional[List[int]] = None  # the residuals, unclipped
    jax_in_window_ns: Optional[int] = None
    jax_trace_before_ns: Optional[int] = None
    # The model's checks that do not lean on its order (read by no metric;
    # for whoever dumps an analysis, and pinned on the recorded timeline):
    launch_order_negative_share: Optional[float] = None  # residuals < 0 under order="launch"
    launch_order_queue_wait_ns: Optional[List[int]] = None
    overdrawn: Optional[int] = None  # dispatches with a positive entry of overdrawn()

    @property
    def window_ns(self) -> int:
        return self.closed - self.opened

    @property
    def negative_residual_share(self) -> Optional[float]:
        r = self.result_return_ns
        return sum(x < 0 for x in r) / len(r) if r else None


def analyse(obs, tl: Optional[dict]) -> Optional[Analysis]:
    if tl is None:
        return None
    found = window(obs, tl)
    if found is None:
        return None
    opened, closed = found
    out = Analysis(opened, closed)

    rows = dispatch_rows(tl, opened)
    kernel_ns = kernel_ns_by_side(obs)
    intervals = None
    if rows is not None and kernel_ns:
        intervals = device_intervals(rows, kernel_ns)
        inside = [(r, s, e) for r, s, e in intervals if opened <= r["t_prep_end"] < closed]
        out.device_queue_wait_ns = [s - r["t_prep_end"] for r, s, _e in inside]
        out.loop_wake_ns = [r["t_resolved"] - r["t_finish_end"] for r, _s, _e in inside]
        out.result_return_ns = residuals(inside)
        fifo = [(r, s, e) for r, s, e in device_intervals(rows, kernel_ns, order="launch")
                if opened <= r["t_prep_end"] < closed]
        if fifo:
            out.launch_order_negative_share = sum(x < 0 for x in residuals(fifo)) / len(fifo)
        out.launch_order_queue_wait_ns = [s - r["t_prep_end"] for r, s, _e in fifo]
        out.overdrawn = sum(x > 0 for x in overdrawn(intervals))

    loop = next((lp for lp in tl["loops"] if lp.get("current")), None)
    if loop is not None and loop["from_ns"] > opened:
        loop = None  # the record starts after the window opens
    if loop is not None:
        slot_ns, idle = loop["slot_ns"], dict(loop["idle"])
        out.loop_idle_ns = idle_ns_between(idle, slot_ns, opened, closed)

    gc = tl["gc"]
    gc_spans = [(t, t + d) for _gen, t, d in gc["rows"]]
    gc_whole = _whole_since(gc_spans[0][1] if gc_spans else None, gc["dropped"], opened)
    if intervals is not None and loop is not None and gc_whole:
        out.classes = attribute(
            opened, closed,
            busy=[(s, e) for _r, s, e in intervals],
            gc=gc_spans,
            dispatch_host=[(r["t_flush"], r["t_prep_end"]) for r in rows],
            unflushed=[(r["t_first_enqueue"], r["t_flush"]) for r in rows],
            slot_ns=slot_ns, idle_by_slot=idle,
        )

    jax = tl["jax"]
    if not jax["dropped"]:  # set-up's rows count too: the ring must be whole
        spans = [(name, t - d, t) for name, t, d in jax["rows"]]
        out.jax_in_window_ns = union_ns(_clipped(((a, b) for _n, a, b in spans), opened, closed))
        out.jax_trace_before_ns = union_ns(
            _clipped(((a, b) for n, a, b in spans if n == JAXPR_TRACE), 0, opened)
        )
    return out


def analysis(obs) -> Optional[Analysis]:
    """:func:`analyse` of the process's timeline, made once per
    ``Observations`` (eleven readers share it) and kept on it."""
    if "_spans" not in obs.__dict__:
        obs.__dict__["_spans"] = analyse(obs, timeline())
    return obs.__dict__["_spans"]


# -- what the readers return ---------------------------------------------


def idle_share(obs, name: str) -> Optional[float]:
    """Share of the window in which the device was idle for ``name``."""
    a = analysis(obs)
    if a is None or a.classes is None:
        return None
    return a.classes[name] / a.window_ns


def loop_idle_share(obs) -> Optional[float]:
    a = analysis(obs)
    if a is None or a.loop_idle_ns is None:
        return None
    return a.loop_idle_ns / a.window_ns


def p50_ms(obs, field: str) -> Optional[float]:
    a = analysis(obs)
    values = None if a is None else getattr(a, field)
    if not values:
        return None
    return percentile(sorted(values), 50) / 1e6


def seconds(obs, field: str) -> Optional[float]:
    a = analysis(obs)
    value = None if a is None else getattr(a, field)
    return None if value is None else value / 1e9
