"""Event-loop lag sampler: GIL/loop saturation as a first-class metric.

A periodic task sleeps a fixed interval and measures how late the loop
woke it (scheduled-vs-actual delta).  On a healthy loop the lag is
microseconds; when pure-Python crypto, a long handler, or GIL pressure
from engine worker threads holds the loop, every timer, heartbeat, and
protocol coroutine is delayed by exactly this much — the blind spot
that made host saturation invisible in the per-stage trace.

Samples land in a mergeable :class:`~minbft_tpu.obs.hist.Log2Histogram`
(one observe per tick — ~20 Hz by default, unmeasurable overhead),
exposed over Prometheus as ``minbft_eventloop_lag_seconds`` (prom.py)
and carried in the flight-recorder dump (``loop_lag`` extra) so the
cluster critical-path merge (obs/critpath.py) can attribute a
loop-saturation segment.

``MINBFT_LOOPLAG_INTERVAL`` overrides the sampling interval in seconds;
``0`` disables the sampler entirely.

Beside the sampler, and always on, the loop's **idle clock**
(:class:`LoopIdleClock`): the time a loop spends blocked in its selector
with nothing runnable, per 10 ms slot of the monotonic clock.  The lag
histogram says how late a timer fires; the idle clock says when the loop
had nothing to do — which is what a reader needs to put an idle device
down to a busy or an idle host (the process timeline,
obs/trace.py ``timeline()``).
"""

from __future__ import annotations

import asyncio
import os
import time
import weakref
from array import array
from typing import List, Optional

from .hist import Log2Histogram

INTERVAL_ENV = "MINBFT_LOOPLAG_INTERVAL"
DEFAULT_INTERVAL = 0.05


class LoopLagSampler:
    """Samples the owning event loop's scheduling lag into ``hist``.

    Single-task, loop-confined: ``start`` must run on the loop being
    measured; ``stop`` cancels the task.  The histogram may be a shared
    one (ReplicaMetrics.loop_lag) — observes are loop-side, scrape
    threads only read (the standard monitoring contract).
    """

    def __init__(self, hist: Optional[Log2Histogram] = None,
                 interval: float = DEFAULT_INTERVAL):
        self.hist = hist if hist is not None else Log2Histogram()
        self.interval = interval
        self._task: Optional[asyncio.Task] = None

    def start(self) -> None:
        if self._task is None:
            self._task = asyncio.get_running_loop().create_task(
                self._run(), name="minbft-looplag"
            )

    async def _run(self) -> None:
        loop = asyncio.get_running_loop()
        interval = self.interval
        hist = self.hist
        while True:
            target = loop.time() + interval
            await asyncio.sleep(interval)
            # sleep() never wakes early; a negative delta here is loop
            # clock weirdness and lands in the hist's negatives counter.
            hist.observe(loop.time() - target)

    def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            self._task = None


def maybe_sampler(hist: Log2Histogram) -> Optional[LoopLagSampler]:
    """A sampler at the env-configured interval, or None when disabled
    (``MINBFT_LOOPLAG_INTERVAL=0``)."""
    try:
        interval = float(os.environ.get(INTERVAL_ENV, "") or DEFAULT_INTERVAL)
    except ValueError:
        interval = DEFAULT_INTERVAL
    if interval <= 0:
        return None
    return LoopLagSampler(hist, interval)


class LoopIdleClock:
    """Nanoseconds one loop was blocked in ``select`` with nothing
    runnable, accumulated per slot of ``SLOT_NS`` of ``time.monotonic_ns``
    in a fixed array (a verify kernel is 9.55 ms: coarser slots could not
    be set against single dispatches).  The array is a ring over time,
    ``SLOTS`` slots long (21 minutes: a cold benchmark run from its
    window to its readers); each cell remembers which slot it holds, so a
    lap never mixes two.  One writer, the loop's own thread."""

    SLOT_NS = 10_000_000
    SLOTS = 1 << 17

    def __init__(self):
        self.since_ns = time.monotonic_ns()
        self._slot = array("q", bytes(8 * self.SLOTS))
        self._idle = array("i", bytes(4 * self.SLOTS))
        # The slot being filled, kept in two ints: a busy loop blocks many
        # times a slot, and most additions touch nothing else.
        self._cur = self.since_ns // self.SLOT_NS
        self._cur_idle = 0

    def add(self, t0: int, t1: int) -> None:
        """The loop was blocked from ``t0`` to ``t1``."""
        slot_ns = self.SLOT_NS
        cur = self._cur
        if t1 // slot_ns == cur and t0 >= cur * slot_ns:
            self._cur_idle += t1 - t0
            return
        mask = self.SLOTS - 1
        slots, idle = self._slot, self._idle
        slots[cur & mask], idle[cur & mask] = cur, self._cur_idle
        t0 = max(t0, t1 - slot_ns * mask)  # a sleep longer than the ring: its tail
        self._cur, self._cur_idle = t1 // slot_ns, 0
        while t0 < t1:
            s = t0 // slot_ns
            end = min(t1, (s + 1) * slot_ns)
            if s == self._cur:
                self._cur_idle = end - t0
            else:
                i = s & mask
                if slots[i] != s:
                    slots[i] = s
                    idle[i] = 0
                idle[i] += end - t0
            t0 = end

    def read(self) -> dict:
        """-> ``{"slot_ns", "from_ns", "idle"}``: ``idle`` lists ``(slot
        number, idle ns)`` for every slot with any idle time since
        ``from_ns``, the earliest instant the record still covers; a slot
        after that and not listed was busy throughout."""
        now = time.monotonic_ns()
        first = max(self.since_ns // self.SLOT_NS, now // self.SLOT_NS - self.SLOTS + 2)
        cur, cur_idle = self._cur, self._cur_idle
        idle = [
            (s, ns) for s, ns in zip(self._slot.tolist(), self._idle.tolist())
            if s >= first and ns and s != cur
        ]
        if cur_idle:
            idle.append((cur, cur_idle))
        return {
            "slot_ns": self.SLOT_NS,
            "from_ns": max(self.since_ns, first * self.SLOT_NS),
            "idle": idle,
        }


# loop -> its clock.  Weak: a closed loop takes its clock with it.
_IDLE_CLOCKS: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def install_idle_clock(loop: asyncio.AbstractEventLoop) -> Optional[LoopIdleClock]:
    """Time ``loop``'s blocking ``select`` calls into a
    :class:`LoopIdleClock`: once per loop, however many replicas run on
    it.  ``BaseSelectorEventLoop._selector`` is private, so a loop that
    has none (or one without ``select``) gets no clock and records
    nothing."""
    clock = _IDLE_CLOCKS.get(loop)
    if clock is not None:
        return clock
    selector = getattr(loop, "_selector", None)
    if selector is None or not hasattr(selector, "select"):
        return None
    clock = LoopIdleClock()
    select, now, add = selector.select, time.monotonic_ns, clock.add

    def timed_select(timeout=None):
        if timeout is not None and timeout <= 0:
            return select(timeout)  # a poll: the loop has work to run
        t0 = now()
        try:
            return select(timeout)
        finally:
            add(t0, now())

    selector.select = timed_select
    _IDLE_CLOCKS[loop] = clock
    return clock


def idle_clocks() -> List[dict]:
    """Every live loop's idle record (:meth:`LoopIdleClock.read`), with
    ``"current": True`` on the caller's own running loop."""
    try:
        running = asyncio.get_running_loop()
    except RuntimeError:
        running = None
    return [
        dict(clock.read(), current=loop is running)
        for loop, clock in list(_IDLE_CLOCKS.items())
    ]
