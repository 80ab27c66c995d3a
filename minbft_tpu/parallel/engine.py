"""Asyncio batching engine for TPU crypto verification.

The reference verifies every signature/UI serially and synchronously in the
message-handling goroutine (reference sample/authentication/crypto.go:79-89
called from core/message-handling.go:409-452 and core/usig-ui.go:62-73).
Here, each protocol task awaits ``BatchVerifier.verify_*`` and the engine:

1. appends the item to the scheme's pending queue,
2. flushes by a **ship-when-idle** policy: if no kernel dispatch is in
   flight, the queue flushes on the next event-loop turn (a lone low-load
   verification never stalls waiting for a batch to fill — the latency
   mitigation from SURVEY.md §7 "hard parts"); while a dispatch *is* in
   flight, items accumulate and flush the moment it completes, so batch
   sizes self-scale to arrival-rate × device-latency (high load fills
   batches with no tuning knob),
3. pads the batch to a fixed bucket size (one compiled kernel per bucket,
   never a recompile from a data-dependent shape),
4. dispatches the jitted kernel on a worker thread (keeping the event loop
   free) and resolves every awaiting future with its lane's verdict.

Quorum waits (reference core/commit.go:108-143's mutex-serialized collector)
thereby become "await one batched verify result" — the BASELINE.json north
star restructuring.

Signing gets the mirror-image treatment (:class:`_SignQueue`): client
REQUEST and replica REPLY signatures are awaitable batch lanes over the
fixed-base comb kernels (ops/p256.py / ops/ed25519.py sign halves), with
the cheap big-int nonce/inverse work vectorized on the host — moving
signature generation off the request critical path (DSig, arxiv
2406.07215) the same way verification already is.  The sign queues are
memo-free (every sign is its own protocol event) and fall back to serial
host signing whenever no healthy device exists — CPU backend, write-off,
or a hung dispatch — with the fallback recorded in :class:`SignStats`.
USIG UI signing deliberately never routes here (counter-after-sign is
serial per key, ref usig.c:66-69).
"""

from __future__ import annotations

import asyncio
import contextlib
import contextvars
import dataclasses
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import trace as obs_trace
from ..obs.hist import Log2Histogram


def _bucket_for(n: int, buckets: Sequence[int]) -> int:
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class _Resolved:
    """Pre-resolved awaitable — a memo hit costs no Future machinery."""

    __slots__ = ("v",)

    def __init__(self, v: bool):
        self.v = v

    def __await__(self):
        if False:  # pragma: no cover — makes this a generator function
            yield
        return self.v


class _Phase:
    """One worker-side phase of a dispatch: a ``TraceAnnotation`` on the
    profiler's clock (a flag test without a session) whose end is also
    stamped into the span on ``CLOCK_MONOTONIC``."""

    __slots__ = ("_t", "_index", "_annotation")

    def __init__(self, t: List[int], index: int, annotation):
        self._t, self._index, self._annotation = t, index, annotation

    def __enter__(self) -> None:
        self._annotation.__enter__()

    def __exit__(self, *exc) -> None:
        self._t[self._index] = time.monotonic_ns()
        self._annotation.__exit__(*exc)


class _DispatchSpan:
    """One dispatch's timeline row in the making (obs/trace.py
    DISPATCH_COLUMNS).  ``_run`` makes it on the loop and sets it in a
    context variable; ``asyncio.to_thread`` copies the context, so the
    dispatcher finds it on its worker thread and stamps its own
    instants there, and ``_run`` writes the whole row once it resumes.
    One writer at a time: the loop before and after the await, the one
    worker in between — but for a dispatch that timed out, whose worker
    runs on; ``_note_dispatch`` therefore reads a copy."""

    __slots__ = ("names", "dispatch_id", "lanes", "flags", "t")

    # Indices into ``t``: the ends of the worker's phases, by name.
    WORKER_START, PREP, LAUNCH, WAIT, FINISH = range(5)

    def __init__(self, names: Dict[str, str], dispatch_id: int):
        self.names = names  # phase -> annotation name (:func:`_phase_names`)
        self.dispatch_id = dispatch_id
        self.lanes = 0
        self.flags = 0
        self.t = [0, 0, 0, 0, 0]

    def phase(self, name: str, index: int) -> _Phase:
        """``minbft/dispatch/<queue>/<name>`` around a worker phase.
        Only device dispatchers call it: they import jax anyway."""
        from jax.profiler import TraceAnnotation

        return _Phase(
            self.t,
            index,
            TraceAnnotation(self.names[name], dispatch_id=self.dispatch_id),
        )


def _phase_names(label: str) -> Dict[str, str]:
    """The annotation names of one queue's dispatches, made once."""
    return {
        phase: f"minbft/dispatch/{label}/{phase}"
        for phase in ("prep", "launch", "wait", "finish", "resolve")
    }


_SPAN: contextvars.ContextVar = contextvars.ContextVar(
    "minbft_dispatch_span", default=None
)


def _worker_span() -> _DispatchSpan:
    """The running dispatch's span, ``t_worker_start`` stamped: the first
    line of every device dispatcher.  A dispatcher called outside
    ``_run`` (a test, a probe) gets one that nothing reads."""
    span = _SPAN.get()
    if span is None:
        span = _DispatchSpan(_DIRECT_NAMES, 0)
    span.t[0] = time.monotonic_ns()
    return span


_DIRECT_NAMES = _phase_names("direct")
# A flush reason's index in a dispatch row; one the table lacks reads "other".
_FLUSH_REASON_IDS = {name: i for i, name in enumerate(obs_trace.FLUSH_REASONS)}
_FLUSH_REASON_OTHER = _FLUSH_REASON_IDS["other"]


@dataclasses.dataclass
class VerifyStats:
    """Engine counters (the observability the reference lacks, SURVEY.md §5)."""

    items: int = 0
    batches: int = 0
    max_batch_seen: int = 0
    padded_lanes: int = 0
    # NOT device time, whatever the name (obs/prom.py exports it, so the
    # name stays): the sum of every dispatch's whole ``await`` on the
    # loop — thread hop, host prep, launch, the wait behind other
    # engines' kernels, the kernel, the result's return and the loop's
    # wake-up.  The dispatch rows (obs/trace.py) separate these.
    device_time_s: float = 0.0
    # Host share of the dispatch: time the worker thread spent preparing
    # and packing the batch (limb conversion, batch inversion, staging
    # writes) BEFORE the kernel call — so host_prep_time_s /
    # device_time_s is the prep share of the dispatch's await.
    host_prep_time_s: float = 0.0
    # The ECDSA queue's per-key comb tables (ops/p256.py): items whose
    # key's table was cached, tables built inside a dispatch's prep (the
    # second use of a key the key store's priming did not name, or of one
    # evicted), and the seconds the builds took (part of host_prep_time_s);
    # items served by one host scalar multiplication instead (a key's first
    # use: a warm-up item, a calibration dispatch, a probe) and their seconds.
    key_table_hits: int = 0
    key_table_builds: int = 0
    key_table_build_s: float = 0.0
    key_table_first_uses: int = 0
    key_table_first_use_s: float = 0.0
    memo_hits: int = 0
    dispatch_timeouts: int = 0  # hung device dispatches rescued on host
    # Flight-recorder gauges (event-loop-side updates only): why each
    # batch shipped ("full" / "idle" / "timer" / "completion" — the
    # ship-when-idle policy made observable), and pre-padding batch
    # occupancy bucketed by log2 size (key = (len(batch)-1).bit_length(),
    # so bucket k holds batches of 2^(k-1) < size <= 2^k items — prom.py
    # labels it with the 2^k upper edge).  Both sum to ``batches``.
    flush_reasons: Dict[str, int] = dataclasses.field(default_factory=dict)
    occupancy: Dict[int, int] = dataclasses.field(default_factory=dict)
    # Queue-wait attribution (ISSUE 8): per-item enqueue→dispatch wait
    # and dispatch→complete service as mergeable log2 histograms, both
    # recorded in _run's loop-side accounting block (so for successful
    # batches count == items; a failed dispatch records neither).
    # Scraped as minbft_{verify,sign}_queue_{wait,service}_seconds and
    # dumped for the critical-path merge (obs/critpath.py).
    queue_wait: Log2Histogram = dataclasses.field(default_factory=Log2Histogram)
    queue_service: Log2Histogram = dataclasses.field(
        default_factory=Log2Histogram
    )

    @property
    def mean_batch(self) -> float:
        return self.items / self.batches if self.batches else 0.0


@dataclasses.dataclass
class SignStats:
    """Sign-queue counters — the sign-side sibling of :class:`VerifyStats`.

    ``host_prep_time_s`` covers BOTH host halves of a dispatch (nonce
    derivation + limb packing before the kernel, batch inversion + scalar
    finish after it); ``device_time_s`` is the whole dispatch ``await``
    (see :class:`VerifyStats`: not device time), so the difference is
    thread hop, launch, device queue, kernel, transfer and loop wake-up.
    ``host_fallback_items`` counts items signed by the serial host
    fallback instead of the device — because the backend is CPU (sign
    device auto-disabled), the device was written off, or a dispatch hung
    past the timeout — so a bench artifact can never pass host signing
    off as device throughput."""

    items: int = 0
    batches: int = 0
    max_batch_seen: int = 0
    padded_lanes: int = 0
    device_time_s: float = 0.0
    host_prep_time_s: float = 0.0
    dispatch_timeouts: int = 0
    host_fallback_items: int = 0
    # See VerifyStats: flush-reason and log2 batch-occupancy gauges,
    # loop-side updates only — and the queue-wait/service span
    # histograms (same recording point and invariants).
    flush_reasons: Dict[str, int] = dataclasses.field(default_factory=dict)
    occupancy: Dict[int, int] = dataclasses.field(default_factory=dict)
    queue_wait: Log2Histogram = dataclasses.field(default_factory=Log2Histogram)
    queue_service: Log2Histogram = dataclasses.field(
        default_factory=Log2Histogram
    )

    @property
    def mean_batch(self) -> float:
        return self.items / self.batches if self.batches else 0.0


class _StagingPool:
    """Recycled host staging buffers for the packed dispatch uploads.

    Dispatchers run on worker threads — up to ``max_inflight`` of them
    concurrently per scheme — so buffers are checked out under a lock and
    returned only after the device results are materialized: a buffer is
    never shared by two in-flight dispatches, and at steady state a
    dispatch allocates nothing — prep writes limbs straight into a
    recycled array and padding is a tail slice-zero instead of
    ``list(items) + [PAD] * k`` re-prepping pad lanes every dispatch.
    """

    def __init__(self, cap: int = 8):
        # ``cap`` bounds free buffers kept per (shape, dtype) — the engine
        # passes its max_inflight (the most dispatches that can hold a
        # buffer of one shape at once), so steady state never drops a
        # recyclable buffer.
        self._cap = max(2, cap)
        self._lock = threading.Lock()
        self._free: Dict[tuple, list] = {}

    def acquire(self, shape: tuple, dtype) -> np.ndarray:
        key = (shape, np.dtype(dtype).str)
        with self._lock:
            stack = self._free.get(key)
            buf = stack.pop() if stack else None
        return np.empty(shape, dtype) if buf is None else buf

    def release(self, buf: np.ndarray) -> None:
        key = (buf.shape, buf.dtype.str)
        with self._lock:
            stack = self._free.setdefault(key, [])
            if len(stack) < self._cap:
                stack.append(buf)


class _DispatchQueue:
    """Shared machinery of the verify and sign queues: ship-when-idle
    flush scheduling, ``max_inflight`` worker dispatch, and the
    hung-dispatch liveness net (timeout → host fallback → write-off →
    out-of-band re-probe).  Subclasses own the pending/resolution policy:
    :class:`_SchemeQueue` dedups (verification is a pure function),
    :class:`_SignQueue` is memo-free by design.
    """

    _WRITE_OFF_AFTER = 3  # CONSECUTIVE hung dispatches before host-only
    _REPROBE_AFTER = 600.0  # s before a written-off device is re-tried
    # A cold kernel compile lands inside the FIRST dispatch: give it
    # headroom so a slow-but-healthy compile is not misread as a hung
    # device.  At the served shapes (block lowering, bucket 512) the
    # longest cold first call is ECDSA verify: about 135 s on the chip
    # tool's v5e machine, most of it Python tracing that no compile
    # cache saves (chip_smoke.py's kernels phase prints the split;
    # Ed25519 verify about 100 s), against dispatch_timeout 90 s x 4 =
    # 360 s here; each queue gets its own first-dispatch allowance.
    # Entry points warm their engines before serving anyway
    # (sample/peer/placement.py).
    _FIRST_TIMEOUT_FACTOR = 4
    KIND = 0  # index into obs/trace.py DISPATCH_KINDS
    LABEL_PREFIX = ""

    def __init__(self, engine: "BatchVerifier", name: str, dispatch):
        self.engine = engine
        self.name = name
        self.dispatch = dispatch  # List[item] -> per-lane results
        # This queue in dispatch rows and trace annotations.
        self.label = self.LABEL_PREFIX + name
        self._obs_queue = engine._obs_queue_id(self.label)
        self._phase_names = _phase_names(self.label)
        # (item, future, enqueue_monotonic_ns): the timestamp feeds the
        # per-item queue-wait histogram at dispatch time.
        self.pending: List[Tuple[object, asyncio.Future, int]] = []
        self._flush_handle: Optional[asyncio.Handle] = None
        self.inflight = 0
        # High-water mark of len(pending) since the last peak snapshot
        # (ISSUE 14): the point-in-time depth gauge samples whatever
        # backlog happens to exist AT scrape time and misses every burst
        # between scrapes — the peak is what capacity planning needs.
        # Updated loop-side in _schedule_flush (every growth path runs
        # through it); read-and-reset from the scrape thread is a pair
        # of GIL-atomic int ops (see queue_depth_peaks).
        self.peak_depth = 0
        self._consecutive_timeouts = 0
        self._device_written_off = False
        self._device_ever_succeeded = False
        self._written_off_at = 0.0
        self._probing = False
        # Strong refs to in-flight _run/_probe tasks: the loop keeps
        # only a weak reference to a running task, so without this set a
        # dispatch task is GC-able mid-flight (the TL601 contract).
        self._bg_tasks: set = set()

    def _spawn(self, coro) -> asyncio.Task:
        task = asyncio.get_running_loop().create_task(coro)
        self._bg_tasks.add(task)
        task.add_done_callback(self._bg_tasks.discard)
        return task

    # -- subclass hooks -----------------------------------------------------

    def _fallback(self):
        """Serial host dispatcher for this queue's items (None: no net)."""
        raise NotImplementedError

    def _device_enabled(self) -> bool:
        """False routes every batch straight to the fallback without
        arming the timeout machinery.  May block (first call can
        initialize the jax backend) — only invoked off-loop."""
        return True

    def _device_enabled_fast(self):
        """Loop-safe view of the device-enabled state: the resolved
        bool, or None when resolution would block (the sign queues'
        backend probe initializes jax on first touch — that must happen
        on a worker thread, never on the event loop)."""
        return True

    def _resolve(self, batch, results, fell_back: bool) -> None:
        """Resolve a completed batch's futures (subclass policy)."""
        raise NotImplementedError

    def _resolve_error(self, batch, e: BaseException) -> None:
        """Resolve a failed batch's futures with the failure."""
        raise NotImplementedError

    async def _run(self, batch, reason: str) -> None:
        """One dispatch: liveness-netted execution, shared accounting,
        then the subclass's resolution policy.  The finally re-flush is
        what implements flush-on-completion (accumulated items ship the
        moment a dispatch slot frees up)."""
        items = [it for it, _f, _t in batch]
        t0_ns = time.monotonic_ns()
        span = _DispatchSpan(self._phase_names, next(obs_trace.DISPATCH_IDS))
        _SPAN.set(span)  # this task's context: the worker thread gets a copy
        try:
            results, fell_back = await self._dispatch_with_fallback(items)
        except Exception as e:
            self._resolve_error(batch, e)
            return
        finally:
            # Loop-atomic: each _run task decrements exactly once, and
            # inflight is only ever read/written between awaits on the
            # event loop — no read-modify-write spans a suspension.
            self.inflight -= 1  # noqa: LD001
            if self.pending:
                self._flush_now("completion")
        t_resolved = time.monotonic_ns()
        dt_ns = t_resolved - t0_ns
        dt = dt_ns * 1e-9
        st = self.stats
        st.items += len(batch)
        st.batches += 1
        st.max_batch_seen = max(st.max_batch_seen, len(batch))
        st.device_time_s += dt
        # Flush-reason and occupancy gauges, counted HERE with batches —
        # not at flush time — so both always sum to ``batches`` (a batch
        # whose dispatch raises is counted in neither, keeping the
        # exported invariant true on error paths too).
        st.flush_reasons[reason] = st.flush_reasons.get(reason, 0) + 1
        # Pre-padding occupancy, log2-bucketed (loop-side — _run's
        # accounting block runs on the event loop like the rest of st).
        # (n-1).bit_length() puts bucket k at 2^(k-1) < size <= 2^k — the
        # documented upper-edge convention, so a full power-of-two batch
        # (the common case under load) lands in ITS bucket, not one up.
        occ = (len(batch) - 1).bit_length()
        st.occupancy[occ] = st.occupancy.get(occ, 0) + 1
        # Queue-wait attribution: per-item enqueue→dispatch wait, and the
        # shared dispatch→complete service span fanned to every lane in
        # one O(1) bulk observe.  Recorded HERE, with the other success
        # accounting, so wait.count == service.count == items for every
        # successful batch (the exported invariant).
        wait_h = st.queue_wait
        for _it, _f, t_enq in batch:
            wait_h.observe_ns(t0_ns - t_enq)
        st.queue_service.observe_ns(dt_ns, len(batch))
        resolve = contextlib.nullcontext()
        if span.t[span.LAUNCH]:
            # A device dispatcher ran, so jax is imported: the loop's
            # side of the dispatch, beside the worker's four phases.
            from jax.profiler import TraceAnnotation

            resolve = TraceAnnotation(
                span.names["resolve"], dispatch_id=span.dispatch_id
            )
        # The callers first: the row is about this dispatch, never in
        # its way.
        with resolve:
            self._resolve(batch, results, fell_back)
        self._note_dispatch(span, batch, reason, t0_ns, t_resolved, fell_back)

    def _note_dispatch(self, span: _DispatchSpan, batch, reason: str,
                       t_flush: int, t_resolved: int, fell_back: bool) -> None:
        """This dispatch's row (obs/trace.py DISPATCH_COLUMNS), written
        once, on the loop, with ``batches``: one row per counted batch.
        An instant the dispatcher did not stamp (a host queue or the
        host fallback has no device phases; verify has no finish) takes
        the one before it, so the eight never decrease."""
        flags = span.flags | (obs_trace.FLAG_FALLBACK if fell_back else 0)
        # A copy: after a timeout the abandoned worker may still be
        # stamping ``span.t`` on its thread.
        t = list(span.t)
        if not t[span.LAUNCH]:
            flags |= obs_trace.FLAG_NO_DEVICE
        prev = t_flush
        for i, v in enumerate(t):
            if v < prev:
                t[i] = prev
            prev = t[i]
        self.engine._obs_ring.push_row((
            span.dispatch_id,
            self.engine.obs_id,
            self._obs_queue,
            self.KIND,
            len(batch),
            span.lanes,
            _FLUSH_REASON_IDS.get(reason, _FLUSH_REASON_OTHER),
            flags,
            batch[0][2],  # the oldest: a queue appends in time order
            t_flush,
            *t,
            t_resolved,
        ))

    # -- flush scheduling ---------------------------------------------------

    def _schedule_flush(self, fut: asyncio.Future) -> asyncio.Future:
        loop = asyncio.get_running_loop()
        # Peak BEFORE any flush decision: this line sees the deepest the
        # backlog ever gets (every submit/submit_many lands here with its
        # items already appended, before _flush_now pops them).
        if len(self.pending) > self.peak_depth:
            self.peak_depth = len(self.pending)  # noqa: LD001
        if len(self.pending) >= self.engine.max_batch:
            self._flush_now("full")
        elif self.inflight == 0 and self._flush_handle is None:
            # Device idle: flush on the next loop turn (after every
            # already-runnable coroutine has had the chance to co-submit),
            # optionally stretched by max_delay to coalesce more.
            if self.engine.max_delay > 0:
                self._flush_handle = loop.call_later(
                    self.engine.max_delay, self._flush_now, "timer"
                )
            else:
                self._flush_handle = loop.call_soon(self._flush_now, "idle")
        # else: a dispatch is in flight — accumulate; its completion flushes.
        return fut

    def _flush_now(self, reason: str = "direct") -> None:
        if self._flush_handle is not None:
            self._flush_handle.cancel()
            self._flush_handle = None
        max_batch = self.engine.max_batch
        while self.pending and self.inflight < self.engine.max_inflight:
            batch = self.pending[:max_batch]
            del self.pending[:max_batch]
            self.inflight += 1
            # The reason rides with the batch and is counted in _run's
            # success accounting alongside ``batches``.
            self._spawn(self._run(batch, reason))

    # -- dispatch with the liveness net -------------------------------------

    async def _dispatch_with_fallback(self, items):
        """Run the dispatcher with a liveness net against a DEVICE FAULT:
        a kernel call that never returns (a wedged chip, a runtime that
        lost its device) would wedge the whole queue — every protocol
        task awaiting a result, forever.  The per-item host path
        computes the same function, so after ``dispatch_timeout`` the same
        items are re-run on the HOST (serial — slow but certain) and the
        hung thread is abandoned; repeated timeouts write the device off
        for this queue entirely (every later batch goes straight to host)
        rather than paying the timeout again and again.  This is error
        handling, not a placement choice: every rescue is counted
        (``dispatch_timeouts``, ``host_fallback_items``) and logged, and
        ``chip_smoke.py`` fails on any.

        Returns ``(results, used_fallback)`` — the flag rides WITH the
        results so callers account items and fallbacks atomically at
        resolution time (a flag on ``self`` would race concurrent
        max_inflight dispatches across the awaits)."""
        fallback = self._fallback()
        timeout = self.engine.dispatch_timeout
        enabled = self._device_enabled_fast()
        if enabled is None:
            # Unresolved (first sign dispatch): the backend probe
            # initializes jax — run it on a worker thread so the event
            # loop (protocol timers, every other coroutine) never
            # stalls behind a backend init.
            enabled = await asyncio.to_thread(self._device_enabled)
        if fallback is not None and not enabled:
            # No healthy device for this queue (e.g. the sign queues on a
            # CPU backend): the host path IS the path — no timeout arming,
            # no write-off bookkeeping, fallback recorded in stats.  This
            # gate deliberately outranks the timeout<=0 shortcut below:
            # disabling the liveness net must not re-route sign batches
            # onto a backend the auto-gate ruled out.
            return await asyncio.to_thread(fallback, items), True
        if fallback is None or timeout <= 0:
            return await asyncio.to_thread(self.dispatch, items), False
        if self._device_written_off:
            # The write-off is a demotion, not a death sentence: after
            # _REPROBE_AFTER a duplicate of this batch re-tries the device
            # OUT-OF-BAND (one at a time — _probing gates) and restores
            # the queue on success.  The live batch always goes straight
            # to the fallback: a probe of a still-dead device must never
            # hold protocol work hostage for its timeout.
            due = time.monotonic() - self._written_off_at >= self._REPROBE_AFTER
            if due and not self._probing:
                self._probing = True
                self._spawn(self._probe(list(items)))
            return await asyncio.to_thread(fallback, items), True
        if not self._device_ever_succeeded:
            # Cold compile may be inside this dispatch — see
            # _FIRST_TIMEOUT_FACTOR.
            timeout *= self._FIRST_TIMEOUT_FACTOR
        task = asyncio.ensure_future(asyncio.to_thread(self.dispatch, items))
        try:
            results = await asyncio.wait_for(asyncio.shield(task), timeout)
            self._consecutive_timeouts = 0  # the device is healthy again
            self._device_ever_succeeded = True
            return results, False
        except asyncio.TimeoutError:
            # Abandon the hung thread; swallow whatever it eventually
            # raises (an abandoned-task exception would otherwise spam
            # "Task exception was never retrieved").
            task.add_done_callback(
                lambda t: t.exception() if not t.cancelled() else None
            )
            self.stats.dispatch_timeouts += 1
            span = _SPAN.get()
            if span is not None:
                span.flags |= obs_trace.FLAG_TIMEOUT
            self._consecutive_timeouts += 1
            if self._consecutive_timeouts >= self._WRITE_OFF_AFTER:
                self._device_written_off = True
                self._written_off_at = time.monotonic()
            import logging

            logging.getLogger("minbft.engine").error(
                "%s device dispatch hung >%ss (%d consecutive%s): "
                "running %d items on host",
                self.name,
                timeout,
                self._consecutive_timeouts,
                "; device written off" if self._device_written_off else "",
                len(items),
            )
            return await asyncio.to_thread(fallback, items), True

    async def _probe(self, items) -> None:
        """Out-of-band re-probe of a written-off device with a duplicate
        of a live batch (the duplicates' results are discarded — the live
        batch resolved via the fallback).  Success restores the device
        queue; failure re-arms the re-probe clock."""
        import logging

        # This task's context is a copy of the live dispatch's: the
        # probe's dispatcher must not stamp into that dispatch's row.
        _SPAN.set(None)
        task = asyncio.ensure_future(asyncio.to_thread(self.dispatch, items))
        try:
            await asyncio.wait_for(
                asyncio.shield(task), self.engine.dispatch_timeout
            )
            self._device_written_off = False
            self._consecutive_timeouts = 0
            self._device_ever_succeeded = True
            logging.getLogger("minbft.engine").warning(
                "%s device recovered on re-probe: restoring device queue",
                self.name,
            )
        except asyncio.TimeoutError:
            task.add_done_callback(
                lambda t: t.exception() if not t.cancelled() else None
            )
            self._written_off_at = time.monotonic()
        except Exception:
            self._written_off_at = time.monotonic()
        finally:
            self._probing = False


class _SchemeQueue(_DispatchQueue):
    """Pending verifications for one scheme, with ship-when-idle flush.

    Verification is a pure function of the item, and one engine typically
    serves a whole cluster (BASELINE.json: one chip verifies for all n
    replicas), so identical items are deduplicated: a memo LRU returns
    known verdicts instantly, and an in-flight map lets concurrent
    duplicates await the same lane instead of occupying n lanes.  (The n
    replicas of a cluster all verify the same client signature and the
    same primary UI — dedup turns those n device verifies into one.)
    """

    _MEMO_CAP = 16384
    # Failed verdicts live in their own, much smaller LRU: a flood of
    # distinct garbage signatures must not evict known-GOOD verdicts and
    # re-drive device traffic for them (round-4 verdict weak #7).  Small
    # because negative hits only matter for byzantine *retransmissions* of
    # the same bad item — there is no protocol reason to remember many.
    _NEG_MEMO_CAP = 512

    def __init__(self, engine: "BatchVerifier", name: str, dispatch):
        super().__init__(engine, name, dispatch)
        self.stats = VerifyStats()
        self._memo: "OrderedDict[object, bool]" = OrderedDict()
        self._neg_memo: "OrderedDict[object, bool]" = OrderedDict()
        self._inflight_futs: Dict[object, asyncio.Future] = {}

    def _fallback(self):
        return self.engine._host_fallback_for(self.name)

    def submit(self, item) -> "asyncio.Future | _Resolved":
        out = self._enqueue(item)
        if self.pending:
            self._schedule_flush(None)
        return out

    def submit_many(self, items) -> list:
        """Batch entry point (the ingest runtime's one-call feed): enqueue
        every item, then schedule ONE flush — the whole bundle lands in
        ``pending`` before any dispatch decision, so a decoded ingest
        bundle becomes at most ceil(len/max_batch) device batches instead
        of racing item-by-item against the idle flush.  Returns one
        awaitable per item (memo hits resolve instantly, duplicates share
        lanes — exactly :meth:`submit`'s semantics, item-wise)."""
        outs = [self._enqueue(it) for it in items]
        if self.pending:
            self._schedule_flush(None)
        return outs

    def _enqueue(self, item) -> "asyncio.Future | _Resolved":
        verdict = self._memo.get(item)
        if verdict is None:
            verdict = self._neg_memo.get(item)
            memo = self._neg_memo
        else:
            memo = self._memo
        if verdict is not None:
            memo.move_to_end(item)
            self.stats.memo_hits += 1
            return _Resolved(verdict)
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        waiters = self._inflight_futs.get(item)
        if waiters is not None:
            # Every duplicate awaiter gets its OWN future (resolved
            # together): sharing one future would let any awaiter's task
            # cancellation cancel it for all of them.
            self.stats.memo_hits += 1
            waiters.append(fut)
            return fut
        self._inflight_futs[item] = [fut]
        self.pending.append((item, fut, time.monotonic_ns()))
        return fut

    def _resolve_error(self, batch, e: BaseException) -> None:
        for it, _f, _t in batch:
            for fut in self._inflight_futs.pop(it, ()):
                if not fut.done():
                    fut.set_exception(e)

    def _resolve(self, batch, results, fell_back: bool) -> None:
        for (it, _f, _t), ok in zip(batch, results):
            ok = bool(ok)
            # Pure function: verdicts (both ways) are stable — but they
            # age out of segregated LRUs so garbage cannot evict good.
            memo = self._memo if ok else self._neg_memo
            memo[it] = ok
            for fut in self._inflight_futs.pop(it, ()):
                if not fut.done():
                    fut.set_result(ok)
        # Loop-confined trims: each popitem is atomic on the event loop
        # and the while re-checks after every one, so interleaving with a
        # concurrent resolve only trims more — no cross-await invariant.
        while len(self._memo) > self._MEMO_CAP:
            self._memo.popitem(last=False)
        while len(self._neg_memo) > self._NEG_MEMO_CAP:
            self._neg_memo.popitem(last=False)


class _SignQueue(_DispatchQueue):
    """Pending signatures for one scheme — the sign-side mirror of
    :class:`_SchemeQueue` (same ship-when-idle flush, bucket padding,
    recycled staging, ``max_inflight`` workers, hung-dispatch fallback)
    with the dedup shortcuts deliberately ABSENT: no memo, no in-flight
    coalescing.  Every submission occupies its own lane — a sign is a
    distinct protocol event under the caller's own key (two replicas
    signing byte-identical REPLY content must each produce and account
    for their own signature), so nothing here may short-circuit on item
    equality.  Contrast the USIG, which must not batch at all: its
    counter is incremented only after each certificate exists
    (ref usig.c:66-69), an inherently serial per-key discipline — USIG
    signing never reaches this queue.
    """

    KIND = 1
    LABEL_PREFIX = "sign_"

    def __init__(self, engine: "BatchVerifier", name: str, dispatch):
        super().__init__(engine, name, dispatch)
        self.stats = SignStats()

    def _fallback(self):
        return self.engine._sign_fallback_for(self.name)

    def _device_enabled(self) -> bool:
        return self.engine._sign_device_enabled()

    def _device_enabled_fast(self):
        # None until the first resolution (reading the backend can
        # block) — see _DispatchQueue._device_enabled_fast.
        return self.engine._sign_on_device

    def submit(self, item) -> asyncio.Future:
        loop = asyncio.get_running_loop()
        fut: asyncio.Future = loop.create_future()
        self.pending.append((item, fut, time.monotonic_ns()))
        return self._schedule_flush(fut)

    def _resolve_error(self, batch, e: BaseException) -> None:
        for _it, fut, _t in batch:
            if not fut.done():
                fut.set_exception(e)

    def _resolve(self, batch, results, fell_back: bool) -> None:
        if fell_back:
            # Accounted HERE, with items, so the two counters can never
            # skew apart (e.g. across a bench warmup stats reset).
            self.stats.host_fallback_items += len(batch)
        for (_it, fut, _t), sig in zip(batch, results):
            if not fut.done():
                fut.set_result(sig)


class BatchVerifier:
    """The TPU-backed batch verification engine.

    Schemes: ``ecdsa_p256`` (items: ((qx, qy), digest32, (r, s))),
    ``hmac_sha256`` (items: (key32, msg32, mac32) bytes), and
    ``ed25519`` (items: (pub32, msg, sig64) bytes).

    ``max_batch`` bounds the device batch (and the largest compiled bucket);
    ``max_delay`` optionally stretches the idle-device flush to coalesce
    more items (0 = flush on the next event-loop turn); ``max_inflight``
    bounds concurrent kernel dispatches per scheme (2 keeps the device fed
    while the next batch accumulates).
    """

    def __init__(
        self,
        max_batch: int = 512,
        max_delay: float = 0.0,
        buckets: Optional[Sequence[int]] = None,
        max_inflight: int = 2,
        mesh=None,
        dispatch_timeout: float = 90.0,
        sign_on_device: Optional[bool] = None,
        device=None,
    ):
        # Sign-queue device placement.  None = auto: the device sign
        # kernels (fixed-base comb k*G / r*B) only beat serial host
        # OpenSSL on a real accelerator — on the CPU backend a sign batch
        # would pad to a full comb-kernel compile for no win, so auto
        # resolves to False there and every sign batch transparently runs
        # the host fallback with the fallback recorded in SignStats
        # (host_fallback_items).  Resolved lazily on first use (reading
        # the backend initializes it); tests force True to exercise the
        # device path on CPU.
        self._sign_on_device = sign_on_device
        # Liveness net against a device fault: a device dispatch that
        # exceeds this many seconds (generous — the first dispatch gets
        # _FIRST_TIMEOUT_FACTOR times it for its cold compile) is
        # abandoned and its items re-verified on host; see
        # _DispatchQueue._dispatch_with_fallback.  0 disables.
        self.dispatch_timeout = dispatch_timeout
        # Multi-chip: pass a jax.sharding.Mesh (parallel.mesh.make_mesh)
        # and every device dispatch routes through the sharded kernels —
        # the batch axis is partitioned over the mesh and XLA lays the
        # per-chip programs out over ICI (BASELINE config[4]'s scaling
        # axis).  A 1-device mesh degenerates to the single-chip kernels.
        self.mesh = mesh if (mesh is not None and mesh.size > 1) else None
        # Home-chip pinning (the multi-device engine pool): a jax device
        # this engine's kernel dispatches run on.  None keeps jax's
        # default placement — byte-identical to the pre-pool engine, and
        # the only mode the C=1 pool uses.  Mutually exclusive with
        # ``mesh`` by construction: a mesh-routed engine stripes across
        # chips, a pinned engine owns one.
        if device is not None and self.mesh is not None:
            raise ValueError("pass either device= (home chip) or mesh=, not both")
        self.device = device
        self._sharded_kernels: Dict[str, object] = {}
        self._sharded_lock = threading.Lock()
        # Stats fields are owned per-field: the event loop owns the counts
        # _run updates; padded_lanes and host_prep_time_s are updated by
        # the DISPATCHER, which runs on a worker thread
        # (asyncio.to_thread) — and max_inflight of them can race the
        # read-modify-write.  All dispatcher-side stats updates go through
        # this lock via _note_prep (tools/analyze lock-discipline
        # enforces it).
        self._stats_lock = threading.Lock()
        self.max_batch = max_batch
        self.max_delay = max_delay
        self.max_inflight = max_inflight
        # Default: a small geometric ladder of padded shapes (8, 32, 128,
        # ..., max_batch).  Each distinct bucket size is a separate kernel
        # compilation, but padding a batch of 3 to max_batch=512 wastes
        # ~170x device compute — the ladder bounds pad waste at 4x while
        # keeping the shape count logarithmic.  Pass explicit buckets (e.g.
        # ``(max_batch,)``) when compilation is the scarcer resource (the
        # unrolled ECDSA kernel).
        if buckets:
            self.buckets = tuple(buckets)
        else:
            ladder = []
            b = 8
            while b < max_batch:
                ladder.append(b)
                b *= 4
            ladder.append(max_batch)
            self.buckets = tuple(ladder)
        if self.buckets[-1] < max_batch:
            # An explicit bucket list smaller than max_batch would hand the
            # dispatchers an unplanned data-dependent shape (ADVICE r1).
            raise ValueError(
                f"largest bucket {self.buckets[-1]} < max_batch {max_batch}"
            )
        if self.mesh is not None:
            # Sharded kernels need every argument's batch axis divisible
            # by the mesh size (mesh.py documents the constraint) — round
            # each bucket up to the next multiple, which also keeps the
            # staging-buffer shapes (keyed by bucket) on the contract.
            from . import mesh as mesh_mod

            self.buckets = tuple(
                sorted({mesh_mod.round_up_to_mesh(self.mesh, b) for b in self.buckets})
            )
        self._queues: Dict[str, _SchemeQueue] = {}
        self._sign_queues: Dict[str, _SignQueue] = {}
        self._staging = _StagingPool(cap=max_inflight)
        # The dispatch record (obs/trace.py): one row per counted batch
        # of every queue, DISPATCH_COLUMNS wide, always recorded — of
        # the kind queue_wait is, at one push a dispatch.  The loop
        # writes; timeline() and the shutdown dump read, from any
        # thread, so the ring is the locked one.  2**12 rows hold a
        # 51 s window of one engine at the benchmark's rates.
        self._obs_ring = obs_trace.MTStageRing(
            1 << 12, width=len(obs_trace.DISPATCH_COLUMNS)
        )
        self._obs_queue_ids: Dict[str, int] = {}
        self.obs_id = obs_trace.register_engine(self)

    # -- flight-recorder surface -------------------------------------------

    def _obs_queue_id(self, name: str) -> int:
        qid = self._obs_queue_ids.get(name)  # GIL-atomic fast path
        if qid is None:
            with self._stats_lock:
                qid = self._obs_queue_ids.get(name)
                if qid is None:
                    qid = len(self._obs_queue_ids)
                    self._obs_queue_ids[name] = qid
        return qid

    def drain_obs_events(self) -> list:
        """This engine's dispatch rows, oldest→newest, as obs/trace.py
        DISPATCH_COLUMNS with queue, kind and flush reason by name."""
        return self.dispatch_rows()[0]

    def dispatch_rows(self) -> Tuple[list, int]:
        """-> (:meth:`drain_obs_events`' rows, rows the ring has
        overwritten)."""
        rows, dropped = self._obs_ring.read()
        # dict() is a C-level copy (GIL-atomic): the loop may be
        # interning a new queue's name while another thread decodes.
        names = {v: k for k, v in dict(self._obs_queue_ids).items()}
        kinds, reasons = obs_trace.DISPATCH_KINDS, obs_trace.FLUSH_REASONS
        return [
            (r[0], r[1], names.get(r[2], f"queue{r[2]}"), kinds[r[3]],
             r[4], r[5], reasons[r[6]]) + r[7:]
            for r in rows
        ], dropped

    def queue_depths(self) -> Dict[str, int]:
        """Items pending per verify queue right now (scrape gauge).
        dict() snapshots the live queue map first — the metrics thread
        iterates while the loop lazily inserts new queues, and a bare
        .items() walk could see the dict resize mid-iteration; len() of
        a loop-owned list is GIL-atomic, never torn."""
        return {name: len(q.pending) for name, q in dict(self._queues).items()}

    def sign_queue_depths(self) -> Dict[str, int]:
        return {
            name: len(q.pending) for name, q in dict(self._sign_queues).items()
        }

    def queue_depth_peaks(self, reset: bool = True) -> Dict[str, int]:
        """High-water mark of each verify queue's depth since the last
        peak snapshot (ISSUE 14 satellite): the committed bench artifact
        and the scrape both want peak backlog, not the instantaneous
        gauge that misses every burst between samples.  ``reset`` rearms
        the mark at the CURRENT depth.  Called from scrape threads: the
        read and the rearm store are each GIL-atomic; a burst landing
        between them is picked up by the next snapshot (never torn,
        possibly attributed one window late — the same benign race the
        loop-confined metrics reads accept)."""
        out: Dict[str, int] = {}
        for name, q in dict(self._queues).items():
            out[name] = max(q.peak_depth, len(q.pending))
            if reset:
                q.peak_depth = len(q.pending)  # noqa: LD001
        return out

    def sign_queue_depth_peaks(self, reset: bool = True) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for name, q in dict(self._sign_queues).items():
            out[name] = max(q.peak_depth, len(q.pending))
            if reset:
                q.peak_depth = len(q.pending)  # noqa: LD001
        return out

    def _device_scope(self):
        """Placement scope for one dispatch: ``jax.default_device`` bound
        to the engine's home chip, or a no-op when unpinned.  Entered on
        the WORKER thread around the kernel call — jax's config scopes
        are thread-local, so concurrent engines pinned to different
        chips never fight over a global default."""
        if self.device is None:
            return contextlib.nullcontext()
        import jax

        return jax.default_device(self.device)

    def _sharded(self, name: str, builder):
        # Dispatchers run on worker threads (max_inflight > 1): lock the
        # memo so two concurrent first dispatches don't both trace and
        # compile the same sharded kernel.
        with self._sharded_lock:
            k = self._sharded_kernels.get(name)
            if k is None:
                k = builder(self.mesh)
                self._sharded_kernels[name] = k
            return k

    # -- queues -------------------------------------------------------------

    def _queue(self, name: str, dispatch) -> _SchemeQueue:
        q = self._queues.get(name)
        if q is None:
            q = _SchemeQueue(self, name, dispatch)
            # Loop-side publish of a fresh queue: a GIL-atomic dict store;
            # worker threads only ever read entries that existed before
            # their dispatch was scheduled.
            self._queues[name] = q  # noqa: LD001
        return q

    def _sign_queue(self, name: str, dispatch) -> _SignQueue:
        q = self._sign_queues.get(name)
        if q is None:
            q = _SignQueue(self, name, dispatch)
            # Loop-side publish (see _queue): a GIL-atomic dict store.
            self._sign_queues[name] = q  # noqa: LD001
        return q

    def _host_fallback_for(self, name: str):
        """Serial host re-verification for a DEVICE queue's items (None
        for the host queues themselves — they have no device to hang on)."""
        return {
            "ecdsa_p256": self._dispatch_ecdsa_host,
            "hmac_sha256": self._dispatch_hmac_host,
            "ed25519": self._dispatch_ed25519_host,
        }.get(name)

    def _sign_fallback_for(self, name: str):
        """Serial host signing for a sign queue's items — the write-off /
        timeout / CPU-backend net.  OpenSSL-backed (hostcrypto picks the
        fast path), so a written-off device degrades to the measured
        ~900 signs/s host floor, never to pure-Python big-int signing."""
        from ..utils import hostcrypto as hc

        return {
            "ecdsa_p256": lambda items: [
                hc.ecdsa_sign(d, digest) for d, digest in items
            ],
            "ed25519": lambda items: [
                hc.ed25519_sign(seed, msg) for seed, msg in items
            ],
        }.get(name)

    def _sign_device_enabled(self) -> bool:
        v = self._sign_on_device
        if v is None:
            import jax

            v = jax.default_backend() != "cpu"
            self._sign_on_device = v
        return v

    def written_off(self) -> List[str]:
        """Names of the queues whose device the liveness net has written
        off (``sign:`` prefixed for the sign side); empty on a healthy
        engine."""
        return [
            name for name, q in dict(self._queues).items()
            if q._device_written_off
        ] + [
            f"sign:{name}" for name, q in dict(self._sign_queues).items()
            if q._device_written_off
        ]

    @property
    def stats(self) -> Dict[str, VerifyStats]:
        # dict() snapshot: scrape threads iterate while the loop inserts
        # new queues (see queue_depths).
        return {name: q.stats for name, q in dict(self._queues).items()}

    @property
    def sign_stats(self) -> Dict[str, SignStats]:
        return {name: q.stats for name, q in dict(self._sign_queues).items()}

    # -- public API ---------------------------------------------------------

    async def verify_ecdsa_p256(
        self, pubkey: Tuple[int, int], digest: bytes, sig: Tuple[int, int]
    ) -> bool:
        q = self._queue("ecdsa_p256", self._dispatch_ecdsa)
        return await q.submit((pubkey, digest, sig))

    async def verify_ecdsa_p256_host(
        self, pubkey: Tuple[int, int], digest: bytes, sig: Tuple[int, int]
    ) -> bool:
        """Host-dispatched queue: same dedup memo as the device queue (one
        engine serves the cluster, so the n replicas' identical signature
        checks collapse to one) without coupling each verification to a
        device round trip (per-dispatch host<->device cost, to be
        measured on the chip)."""
        q = self._queue("ecdsa_p256_host", self._dispatch_ecdsa_host)
        return await q.submit((pubkey, digest, sig))

    async def verify_hmac_sha256(self, key: bytes, msg32: bytes, mac: bytes) -> bool:
        q = self._queue("hmac_sha256", self._dispatch_hmac)
        return await q.submit((key, msg32, mac))

    async def verify_hmac_sha256_host(
        self, key: bytes, msg32: bytes, mac: bytes
    ) -> bool:
        q = self._queue("hmac_sha256_host", self._dispatch_hmac_host)
        return await q.submit((key, msg32, mac))

    async def verify_ed25519(self, pub: bytes, msg: bytes, sig: bytes) -> bool:
        q = self._queue("ed25519", self._dispatch_ed25519)
        return await q.submit((pub, msg, sig))

    async def verify_ed25519_host(self, pub: bytes, msg: bytes, sig: bytes) -> bool:
        q = self._queue("ed25519_host", self._dispatch_ed25519_host)
        return await q.submit((pub, msg, sig))

    async def _verify_many(self, name: str, dispatch, items) -> list:
        """Whole-bundle verification feed (the batch-ingest runtime's one
        engine call per decoded bundle): every item lands in the queue
        before ONE flush decision, so an N-item bundle dispatches as
        ~N/max_batch device batches instead of N racing idle flushes.
        Returns per-item verdicts in input order."""
        q = self._queue(name, dispatch)
        outs = q.submit_many(items)
        # Gather with return_exceptions so EVERY lane's outcome is
        # consumed even when the batch errors — awaiting sequentially
        # would abandon lanes 2..N after the first raise and spam
        # "Future exception was never retrieved" at GC.
        results = await asyncio.gather(*outs, return_exceptions=True)
        for r in results:
            if isinstance(r, BaseException):
                raise r
        return list(results)

    async def verify_ecdsa_p256_many(self, items) -> list:
        """Batch sibling of :meth:`verify_ecdsa_p256`:
        ``items = [((qx, qy), digest32, (r, s)), ...]`` -> [bool, ...]."""
        return await self._verify_many("ecdsa_p256", self._dispatch_ecdsa, items)

    async def verify_ecdsa_p256_host_many(self, items) -> list:
        return await self._verify_many(
            "ecdsa_p256_host", self._dispatch_ecdsa_host, items
        )

    async def verify_ed25519_many(self, items) -> list:
        """Batch sibling of :meth:`verify_ed25519`:
        ``items = [(pub32, msg, sig64), ...]`` -> [bool, ...]."""
        return await self._verify_many("ed25519", self._dispatch_ed25519, items)

    async def verify_ed25519_host_many(self, items) -> list:
        return await self._verify_many(
            "ed25519_host", self._dispatch_ed25519_host, items
        )

    async def verify_nist_host(
        self, curve: str, pub: bytes, msg: bytes, sig: bytes
    ) -> bool:
        """Host-queue verification for the wider NIST curves (P-384/P-521
        have no TPU kernel): worker-thread OpenSSL behind the same dedup
        memo + thread-hop batching as the other host queues."""
        name = f"ecdsa_{curve}_host"
        q = self._queues.get(name)
        if q is None:
            from ..utils import hostcrypto as hc

            def dispatch(items, _curve=curve):
                return np.array(
                    [hc.nist_verify(_curve, p, m, s) for p, m, s in items],
                    dtype=bool,
                )

            q = self._queue(name, dispatch)
        return await q.submit((pub, msg, sig))

    # -- signing ------------------------------------------------------------
    #
    # The awaitable batch sign surface (DSig's off-critical-path signing
    # restructured for TPU): protocol tasks await a lane, the queue ships
    # fixed-bucket batches of k*G / r*B through the fixed-base comb
    # kernels, and the cheap big-int scalar work (RFC 6979 / RFC 8032
    # nonces, one Montgomery batch inversion per batch) stays on the
    # host — see ops/p256.py sign_prepare/sign_finish.  USIG UI signing
    # must NEVER route here: its counter is incremented only after the
    # certificate exists (ref usig.c:66-69), a serial per-key discipline.

    async def sign_ecdsa_p256(self, d: int, digest: bytes) -> Tuple[int, int]:
        """Batch-sign ``digest`` under private scalar ``d`` -> (r, s).
        RFC 6979 deterministic — byte-identical to
        ``hostcrypto.ecdsa_sign_py`` on the device path; the host
        fallback signs with OpenSSL (random nonce, equally valid)."""
        q = self._sign_queue("ecdsa_p256", self._dispatch_sign_ecdsa)
        return await q.submit((d, digest))

    async def sign_ed25519(self, seed: bytes, msg: bytes) -> bytes:
        """Batch-sign ``msg`` under ``seed`` -> 64-byte RFC 8032
        signature (deterministic on every path)."""
        q = self._sign_queue("ed25519", self._dispatch_sign_ed25519)
        return await q.submit((seed, msg))

    # -- warm-up ------------------------------------------------------------

    def load_kernels(self, schemes) -> None:
        """Load on the calling thread the executable of every device
        kernel that one item of ``schemes`` reaches through this engine
        (verify, and sign where signing is on the device), at the bucket
        one item lands in, on this engine's chip:
        ``ops/lowering.py::per_mode_jit`` loads it from the kernel store
        and serves every later call of that key with it, so that a
        dispatcher's first call, on its worker thread, loads nothing.  The
        runtime loads an executable 5-6 times slower on a thread that is
        not the process's main one (glibc's arena a thread: PERF.md
        section 6).  Blocks the caller for the loads.  Nothing is loaded
        where there is no store (the CPU backend), no entry (the first
        dispatch builds it, as before: a build on this thread measured
        slower, PERF.md section 6) or a key resolved already, and a
        mesh-routed engine's kernels keep the plain jit."""
        if self.mesh is not None:
            return
        sign = self._sign_device_enabled()
        lanes = _bucket_for(1, self.buckets)
        with self._device_scope():
            if "ecdsa_p256" in schemes:
                from ..ops import p256

                p256.ecdsa_verify_kernel_packed.resolve(
                    ((lanes, p256.PACKED_COLS), np.uint16)
                )
                if sign:
                    p256.kg_comb_kernel().resolve(((lanes, p256.SIGN_COLS), np.uint16))
            if "ed25519" in schemes:
                from ..ops import ed25519 as ed

                ed.ed25519_verify_kernel_packed.resolve(
                    ((lanes, ed.PACKED_COLS), np.uint16)
                )
                if sign:
                    ed.rb_comb_kernel().resolve(((lanes, ed.SIGN_COLS), np.uint16))
            if "hmac_sha256" in schemes:
                from ..ops.hmac_sha256 import hmac_verify_kernel_packed

                # key | msg | mac, eight big-endian words each (_dispatch_hmac)
                hmac_verify_kernel_packed.resolve(((lanes, 24), np.uint32))

    # -- dispatchers (worker thread; jax work happens here) -----------------
    #
    # Shape: acquire a recycled staging buffer, prep/pack the batch into
    # it (timed separately as host_prep_time_s — the prep/device split is
    # a first-class measurement), dispatch the kernel, materialize the
    # results, release the buffer.  The release MUST stay behind the
    # result materialization: jax may still be reading the host buffer
    # until the dispatch completes, and a released buffer can be
    # re-acquired and overwritten by a concurrent dispatcher.

    def _note_prep(self, name: str, pad: int, prep_s: float, tables=None) -> None:
        """Cross-thread stats update for a dispatcher (worker thread):
        padded-lane and host-prep accounting under the stats lock;
        ``tables`` is the ECDSA prep's ``p256.KeyTableTally``."""
        with self._stats_lock:
            st = self._queues[name].stats
            st.padded_lanes += pad
            st.host_prep_time_s += prep_s
            if tables is not None:
                st.key_table_hits += tables.hits
                st.key_table_builds += tables.builds
                st.key_table_build_s += tables.build_s
                st.key_table_first_uses += tables.first_uses
                st.key_table_first_use_s += tables.first_use_s

    def _note_sign_prep(self, name: str, pad: int, prep_s: float) -> None:
        """Sign-queue sibling of :meth:`_note_prep` (worker thread):
        same lock, the SignStats of ``_sign_queues[name]``."""
        with self._stats_lock:
            st = self._sign_queues[name].stats
            st.padded_lanes += pad
            st.host_prep_time_s += prep_s

    def _dispatch_ecdsa(self, items) -> np.ndarray:
        span = _worker_span()
        import jax.numpy as jnp

        from ..ops import p256

        n = len(items)
        b = span.lanes = _bucket_for(n, self.buckets)
        # Packed single-upload form: one host->device transfer per
        # dispatch, the lanes' comb-table rows included (ops/p256.py).
        t0 = time.perf_counter()
        staging = self._staging.acquire((b, p256.PACKED_COLS), np.uint16)
        tables = p256.KeyTableTally()
        try:
            with span.phase("prep", span.PREP):
                packed = p256.prepare_packed(items, b, out=staging, tally=tables)
            self._note_prep(
                "ecdsa_p256", b - n, time.perf_counter() - t0, tables
            )
            if self.mesh is not None:
                from . import mesh as mesh_mod

                kernel = self._sharded("ecdsa", mesh_mod.sharded_ecdsa_kernel)
            else:
                kernel = p256.ecdsa_verify_kernel_packed
            with self._device_scope():
                with span.phase("launch", span.LAUNCH):
                    out = kernel(
                        packed if self.mesh is not None else jnp.asarray(packed)
                    )
                with span.phase("wait", span.WAIT):
                    return np.asarray(out)[:n]
        finally:
            self._staging.release(staging)

    def _dispatch_hmac(self, items) -> np.ndarray:
        span = _worker_span()
        import jax.numpy as jnp

        from ..ops.hmac_sha256 import hmac_verify_kernel_packed

        n = len(items)
        b = span.lanes = _bucket_for(n, self.buckets)
        t0 = time.perf_counter()
        staging = self._staging.acquire((b, 24), np.uint32)
        try:
            with span.phase("prep", span.PREP):
                # One bulk big-endian word view of the concatenated batch
                # instead of 3n per-item frombuffer calls.
                staging[:n] = np.frombuffer(
                    b"".join([key + msg + mac for key, msg, mac in items]),
                    dtype=">u4",
                ).reshape(n, 24)
                staging[n:] = 0
            self._note_prep("hmac_sha256", b - n, time.perf_counter() - t0)
            if self.mesh is not None:
                from . import mesh as mesh_mod

                kernel = self._sharded("hmac", mesh_mod.sharded_hmac_kernel)
            else:
                kernel = hmac_verify_kernel_packed
            with self._device_scope():
                with span.phase("launch", span.LAUNCH):
                    out = kernel(
                        staging if self.mesh is not None else jnp.asarray(staging)
                    )
                with span.phase("wait", span.WAIT):
                    return np.asarray(out)[:n]
        finally:
            self._staging.release(staging)

    def _dispatch_ed25519(self, items) -> np.ndarray:
        span = _worker_span()
        import jax.numpy as jnp

        from ..ops import ed25519 as ed

        n = len(items)
        b = span.lanes = _bucket_for(n, self.buckets)
        t0 = time.perf_counter()
        staging = self._staging.acquire((b, ed.PACKED_COLS), np.uint16)
        try:
            with span.phase("prep", span.PREP):
                packed = ed.prepare_packed(items, b, out=staging)
            self._note_prep("ed25519", b - n, time.perf_counter() - t0)
            if self.mesh is not None:
                from . import mesh as mesh_mod

                kernel = self._sharded("ed25519", mesh_mod.sharded_ed25519_kernel)
            else:
                kernel = ed.ed25519_verify_kernel_packed
            with self._device_scope():
                with span.phase("launch", span.LAUNCH):
                    out = kernel(
                        packed if self.mesh is not None else jnp.asarray(packed)
                    )
                with span.phase("wait", span.WAIT):
                    return np.asarray(out)[:n]
        finally:
            self._staging.release(staging)

    # Sign dispatchers: prep (host) → comb kernel (device) → finish
    # (host), with the nonce-limb staging recycled through the pool and
    # BOTH host halves timed into SignStats.host_prep_time_s.  The
    # staging release stays behind the result materialization, exactly
    # like the verify dispatchers.

    def _dispatch_sign_ecdsa(self, items) -> list:
        span = _worker_span()
        from ..ops import p256

        n = len(items)
        b = span.lanes = _bucket_for(n, self.buckets)
        t0 = time.perf_counter()
        staging = self._staging.acquire((b, p256.SIGN_COLS), np.uint16)
        try:
            with span.phase("prep", span.PREP):
                k_arr, meta = p256.sign_prepare(items, b, out=staging)
            prep = time.perf_counter() - t0
            if self.mesh is not None:
                from . import mesh as mesh_mod

                kernel = self._sharded(
                    "ecdsa_sign", mesh_mod.sharded_ecdsa_sign_kernel
                )
            else:
                kernel = p256.ecdsa_kg_kernel
            with self._device_scope():
                with span.phase("launch", span.LAUNCH):
                    out = kernel(k_arr)
                with span.phase("wait", span.WAIT):
                    xz = np.asarray(out)
            t1 = time.perf_counter()
            with span.phase("finish", span.FINISH):
                sigs = p256.sign_finish(items, meta, xz)
            prep += time.perf_counter() - t1
            self._note_sign_prep("ecdsa_p256", b - n, prep)
            return sigs
        finally:
            self._staging.release(staging)

    def _dispatch_sign_ed25519(self, items) -> list:
        span = _worker_span()
        from ..ops import ed25519 as ed

        n = len(items)
        b = span.lanes = _bucket_for(n, self.buckets)
        t0 = time.perf_counter()
        staging = self._staging.acquire((b, ed.SIGN_COLS), np.uint16)
        try:
            with span.phase("prep", span.PREP):
                r_arr, meta = ed.sign_prepare(items, b, out=staging)
            prep = time.perf_counter() - t0
            if self.mesh is not None:
                from . import mesh as mesh_mod

                kernel = self._sharded(
                    "ed25519_sign", mesh_mod.sharded_ed25519_sign_kernel
                )
            else:
                kernel = ed.ed25519_rb_kernel
            with self._device_scope():
                with span.phase("launch", span.LAUNCH):
                    out = kernel(r_arr)
                with span.phase("wait", span.WAIT):
                    xyz = np.asarray(out)
            t1 = time.perf_counter()
            with span.phase("finish", span.FINISH):
                sigs = ed.sign_finish(meta, xyz)
            prep += time.perf_counter() - t1
            self._note_sign_prep("ed25519", b - n, prep)
            return sigs
        finally:
            self._staging.release(staging)

    # Host dispatchers: serial OpenSSL in the worker thread — no padding,
    # no device round trip; the queue layer still provides batching of the
    # thread hops plus the dedup memo.

    def _dispatch_ecdsa_host(self, items) -> np.ndarray:
        from ..utils import hostcrypto as hc

        return np.array(
            [hc.ecdsa_verify(q, digest, sig) for q, digest, sig in items],
            dtype=bool,
        )

    def _dispatch_hmac_host(self, items) -> np.ndarray:
        import hashlib
        import hmac as hmac_mod

        return np.array(
            [
                hmac_mod.compare_digest(
                    hmac_mod.new(key, msg, hashlib.sha256).digest(), mac
                )
                for key, msg, mac in items
            ],
            dtype=bool,
        )

    def _dispatch_ed25519_host(self, items) -> np.ndarray:
        from ..utils import hostcrypto as hc

        return np.array(
            [hc.ed25519_verify(pub, msg, sig) for pub, msg, sig in items],
            dtype=bool,
        )
