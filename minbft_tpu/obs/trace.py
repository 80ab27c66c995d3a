"""Protocol flight recorder: per-request stage spans.

Every pipeline hook notes a (request-key, stage, monotonic-ns) event:

- **replica** capture points: ``recv`` → ``verify_enqueue`` →
  ``verify_done`` → ``prepare`` → ``commit_quorum`` → ``execute`` →
  ``reply_sign`` → ``reply_sent``;
- **client** capture points: ``start`` → ``sign`` → ``broadcast`` →
  ``first_reply`` → ``quorum``.

Two artifacts come out of a note:

1. the raw event lands in a **preallocated ring buffer** (forensics:
   the JSON trace dump carries the tail of the run, request by request);
2. the duration since the request's PREVIOUS noted point is folded into
   that stage's :class:`~minbft_tpu.obs.hist.Log2Histogram` — so
   ``stage_commit_quorum`` reads "time from prepare to commit quorum",
   and the histograms answer "where does a committed request's time go"
   without post-processing (and merge across replicas, unlike a
   reservoir).

Cost discipline (the ISSUE's contract): with tracing disabled every hook
is ONE predicated attribute check (``if tr is not None``) — the recorder
simply doesn't exist.  Enabled, a note is two dict operations, four
array stores into the preallocated ring, and one histogram increment; no
per-event object survives the call.

Threading: a :class:`StageRing` has a SINGLE writer (the event loop) and
is deliberately lock-free — asyncio callbacks never preempt mid-push.
Engine worker threads must never touch it; they get their own
:class:`MTStageRing`, whose push/drain are serialized by its lock (the
same locked-writes discipline as the engine's ``_stats_lock`` stats;
``tools/analyze`` lock-discipline enforces both).
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import threading
import time
import weakref
from array import array
from typing import Dict, Iterable, List, Optional, Tuple

from .hist import Log2Histogram

# Replica capture points, in pipeline order.  ``ingest`` is the
# bundle-runtime entry (the tick that decoded this request's frame
# bundle); ``recv`` is the per-message entry (``handle_client_message``)
# — both are ENTRY stages (they open spans, never record durations), so
# retransmit gaps can't pollute the cost table.
REPLICA_STAGES: Tuple[str, ...] = (
    "ingest",
    "recv",
    "verify_enqueue",
    "verify_done",
    "prepare",
    "commit_quorum",
    "execute",
    "reply_sign",
    "reply_sent",
)
R_INGEST = 0
R_RECV = 1
R_VERIFY_ENQUEUE = 2
R_VERIFY_DONE = 3
R_PREPARE = 4
R_COMMIT_QUORUM = 5
R_EXECUTE = 6
R_REPLY_SIGN = 7
R_REPLY_SENT = 8
# Stages that never close a span (see FlightRecorder.note).
_REPLICA_ENTRY_STAGES = frozenset((R_INGEST, R_RECV))

# Client capture points ("start" is the implicit entry of request()).
CLIENT_STAGES: Tuple[str, ...] = (
    "start",
    "sign",
    "broadcast",
    "first_reply",
    "quorum",
)
C_START = 0
C_SIGN = 1
C_BROADCAST = 2
C_FIRST_REPLY = 3
C_QUORUM = 4

# Environment knobs (read once per recorder construction, never per event).
TRACE_ENV = "MINBFT_TRACE"
TRACE_DUMP_ENV = "MINBFT_TRACE_DUMP"
_RING_ENV = "MINBFT_TRACE_RING"

_DEFAULT_RING = 1 << 15
# In-flight pairing state is bounded: a key whose final stage never
# arrives (dropped request) would leak its entry, so past this many keys
# the map is reset wholesale — losing pairing for the requests in flight
# at that instant, never memory.
_MAX_INFLIGHT_KEYS = 1 << 16


def tracing_enabled() -> bool:
    """True when the operator asked for tracing: ``MINBFT_TRACE`` set to
    anything but the usual falsy spellings (so ``MINBFT_TRACE=0``
    DISABLES, matching the repo's env-flag convention), or a
    ``MINBFT_TRACE_DUMP`` path (any non-empty value — it names a file
    prefix, not a flag)."""
    flag = os.environ.get(TRACE_ENV, "")
    if flag.lower() not in ("", "0", "false", "no"):
        return True
    return bool(os.environ.get(TRACE_DUMP_ENV))


class StageRing:
    """Preallocated single-writer ring of fixed-width rows of integers
    in one ``array('q')`` (the flight recorder's events are the
    four-wide ``(a, b, stage, t_ns)``; the dispatch record is sixteen
    wide).

    :meth:`push` (four-wide rings: the per-request hot path) is four
    C-level stores plus three int updates — no allocation, no lock;
    :meth:`push_row` (any width, once a dispatch / pass / event) is one
    slice store.  ONLY the owning
    event loop may push; cross-thread producers use :class:`MTStageRing`.
    :meth:`read` also counts the rows a wrapped ring has overwritten, so
    a reader can tell a whole record from the tail of one.
    """

    __slots__ = ("_buf", "_width", "_cap", "_idx", "_n", "_pushed")

    def __init__(self, capacity: int = _DEFAULT_RING, width: int = 4):
        cap = 1
        while cap < max(2, capacity):
            cap <<= 1
        self._cap = cap
        self._width = width
        self._buf = array("q", bytes(8 * cap * width))
        self._idx = 0  # next write slot
        self._n = 0  # valid entries (saturates at _cap)
        self._pushed = 0  # rows ever pushed

    def push(self, a: int, b: int, c: int, t_ns: int) -> None:
        if self._width != 4:
            raise ValueError(f"push() on a ring of {self._width} columns")
        i = self._idx
        buf, j = self._buf, i << 2
        buf[j] = a
        buf[j + 1] = b
        buf[j + 2] = c
        buf[j + 3] = t_ns
        self._idx = (i + 1) & (self._cap - 1)
        self._pushed += 1
        if self._n < self._cap:
            self._n += 1

    def push_row(self, row: Tuple[int, ...]) -> None:
        w = self._width
        if len(row) != w:  # a slice store of another length would resize the buffer
            raise ValueError(f"row of {len(row)} for a ring of {w} columns")
        i = self._idx
        self._buf[i * w:(i + 1) * w] = array("q", row)
        self._idx = (i + 1) & (self._cap - 1)
        self._pushed += 1
        if self._n < self._cap:
            self._n += 1

    def __len__(self) -> int:
        return self._n

    @property
    def capacity(self) -> int:
        return self._cap

    def snapshot(self, limit: Optional[int] = None) -> List[Tuple[int, ...]]:
        """Rows oldest→newest (optionally only the newest ``limit``)."""
        n = self._n
        if limit is not None:
            n = min(n, limit)
        w = self._width
        start = (self._idx - n) & (self._cap - 1)
        head = min(n, self._cap - start)
        # Two slices (the ring's tail, then its wrapped head), cut into rows.
        flat = (
            self._buf[start * w:(start + head) * w].tolist()
            + self._buf[:(n - head) * w].tolist()
        )
        return [tuple(flat[k:k + w]) for k in range(0, n * w, w)]

    def read(self) -> Tuple[List[Tuple[int, ...]], int]:
        """``(snapshot(), rows overwritten since the ring was made)`` of
        one instant."""
        # The base snapshot by name: MTStageRing.read holds its lock here.
        return StageRing.snapshot(self), self._pushed - self._n


class MTStageRing(StageRing):
    """Multi-producer sibling of :class:`StageRing`: engine worker
    threads (up to ``max_inflight`` concurrent dispatchers) push under
    the ring's lock, and drains hold the same lock — the locked-writes
    discipline ``tools/analyze`` enforces for every cross-thread
    mutation in this codebase.  Same storage/wrap semantics as the
    base; only the lock wrapping differs."""

    __slots__ = ("_lock",)

    def __init__(self, capacity: int = 4096, width: int = 4):
        super().__init__(capacity, width)
        self._lock = threading.Lock()

    def push(self, a: int, b: int, c: int, t_ns: int) -> None:
        with self._lock:
            super().push(a, b, c, t_ns)

    def push_row(self, row: Tuple[int, ...]) -> None:
        with self._lock:
            super().push_row(row)

    def __len__(self) -> int:
        with self._lock:
            return super().__len__()

    def snapshot(self, limit: Optional[int] = None) -> List[Tuple[int, ...]]:
        with self._lock:
            return super().snapshot(limit)

    def read(self) -> Tuple[List[Tuple[int, ...]], int]:
        with self._lock:
            return super().read()


class FlightRecorder:
    """Stage-span recorder for one replica or client.

    ``note(stage, cid, seq)`` is THE hot-path entry point; everything
    else (snapshots, dumps, tables) is cold-path reporting.  Histograms
    may be read by a scrape thread while the loop writes — int mutations
    are GIL-atomic, so a reader sees a slightly stale but never torn
    view (standard monitoring semantics).
    """

    def __init__(
        self,
        kind: str,
        ident: int,
        stages: Tuple[str, ...],
        ring_capacity: Optional[int] = None,
        entry_stages: Optional[frozenset] = None,
        group: Optional[int] = None,
    ):
        if ring_capacity is None:
            ring_capacity = int(os.environ.get(_RING_ENV, _DEFAULT_RING))
        self.kind = kind  # "replica" | "client" | "engine"
        self.ident = ident
        # Consensus-group id (multi-group runtime): stamped into dumps so
        # stage_table/critpath_table can filter one group's spans out of
        # a shared-process dump set; None = ungrouped.
        self.group = group
        self.stages = stages
        self.ring = StageRing(ring_capacity)
        self.hists: List[Log2Histogram] = [Log2Histogram() for _ in stages]
        self._final = len(stages) - 1
        # Pipeline entries: stages that open a span but never close one
        # (a retransmission re-noting an entry mid-pipeline must not fold
        # its gap into the cost table).  Default: stage 0 only.
        self._entries = frozenset((0,)) if entry_stages is None else entry_stages
        # (cid, seq) -> monotonic-ns of the previous noted point.
        self._last: Dict[Tuple[int, int], int] = {}

    @staticmethod
    def for_replica(
        replica_id: int, group: Optional[int] = None
    ) -> "FlightRecorder":
        return FlightRecorder(
            "replica",
            replica_id,
            REPLICA_STAGES,
            entry_stages=_REPLICA_ENTRY_STAGES,
            group=group,
        )

    @staticmethod
    def for_client(
        client_id: int, group: Optional[int] = None
    ) -> "FlightRecorder":
        return FlightRecorder("client", client_id, CLIENT_STAGES, group=group)

    def note(self, stage: int, cid: int, seq: int) -> None:
        t = time.monotonic_ns()
        self.ring.push(cid, seq, stage, t)
        key = (cid, seq)
        last = self._last
        prev = last.get(key)
        if prev is not None and stage not in self._entries:
            # Entry stages (ingest/recv on replicas, start on clients)
            # open spans but never close one — a client retransmission
            # re-noting an entry mid-pipeline would otherwise fold the
            # 30s retransmit gap into the cost table as "recv time".
            # (The raw ring still keeps the duplicate arrival for
            # forensics.)
            self.hists[stage].observe_ns(t - prev)
        if stage == self._final:
            last.pop(key, None)
        else:
            if len(last) >= _MAX_INFLIGHT_KEYS:
                last.clear()
            last[key] = t

    # -- reporting ------------------------------------------------------

    def stage_hists(self) -> Dict[str, Log2Histogram]:
        """Stage name -> histogram of "time from the previous noted
        point to this point" (entry points with no predecessor record
        nothing)."""
        return {
            name: h
            for name, h in zip(self.stages, self.hists)
            if h.count
        }

    def to_dict(self, max_events: int = 4096) -> dict:
        doc = {
            "kind": self.kind,
            "id": self.ident,
            "stages": list(self.stages),
            "clock_domain": clock_domain(),
            "hists": {n: h.to_dict() for n, h in self.stage_hists().items()},
            "events": [
                list(e) for e in self.ring.snapshot(limit=max_events)
            ],
        }
        if self.group is not None:
            doc["group"] = self.group
        return doc


# ---------------------------------------------------------------------------
# The process timeline: what the host was doing, at dispatch /
# collector-pass / JAX-event / request granularity, always recorded (no
# switch: these recorders are of the kind the engine's queue_wait
# histograms are), every instant ``time.monotonic_ns()``.  The event
# loop's idle clock is the fifth part (obs/looplag.py).  One way in:
# :func:`timeline`.

# One row per counted batch of every engine queue (parallel/engine.py
# _DispatchQueue._note_dispatch writes it, on the loop).
DISPATCH_COLUMNS: Tuple[str, ...] = (
    "dispatch_id",  # process-wide, from DISPATCH_IDS
    "engine",  # register_engine's id
    "queue",  # e.g. ecdsa_p256, sign_ecdsa_p256
    "kind",  # DISPATCH_KINDS
    "items",
    "lanes",  # the bucket the batch was padded to (0: no device phases)
    "reason",  # FLUSH_REASONS
    "flags",  # FLAG_*
    # The eight instants, never decreasing:
    "t_first_enqueue",  # the batch's oldest item entered the queue
    "t_flush",  # _run began: the batch left the queue
    "t_worker_start",  # the dispatcher's first line, on its thread
    "t_prep_end",  # packed buffer ready
    "t_launch_end",  # the jitted call returned: the kernel is enqueued
    "t_result",  # np.asarray returned: the result is on the host
    "t_finish_end",  # after sign_finish (= t_result for verify)
    "t_resolved",  # _run running again on the loop
)
DISPATCH_KINDS: Tuple[str, ...] = ("verify", "sign")
FLUSH_REASONS: Tuple[str, ...] = ("direct", "full", "idle", "timer", "completion", "other")
FLAG_FALLBACK = 1  # the host fallback computed the results
FLAG_TIMEOUT = 2  # the device dispatch hung past dispatch_timeout
FLAG_NO_DEVICE = 4  # no dispatcher stamped a launch: a host queue, or the fallback alone
DISPATCH_IDS = itertools.count(1)  # next() is one C call: safe from any thread

# JAX's own duration events (jax.monitoring), as JAX 0.9.0 names them
# (tests/test_jaxcache.py pins the names against the installed JAX).
JAX_EVENTS: Tuple[str, ...] = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
    "/jax/compilation_cache/cache_retrieval_time_sec",
)
_JAX_EVENT_IDS = {name: i for i, name in enumerate(JAX_EVENTS)}

# Collector passes of generations 0 and 1 and JAX events are recorded from
# 1 ms up: a kernel's trace nests tens of thousands of inner traces of
# microseconds each, all inside the outer event that is recorded.
_MIN_NS = 1_000_000

_ENGINE_IDS = itertools.count()
# Weak: the registry keeps no engine alive, and a dead one's rows go with it.
_ENGINES: "weakref.WeakValueDictionary[int, object]" = weakref.WeakValueDictionary()
# (client id, seq, C_START, t): one row a request, from every client of
# the process (clients may live on several loops, so the locked ring).
# 2**16 rows hold set-up and 100 s of windows at 600 requests/s.
_CLIENT_ROWS = MTStageRing(1 << 16)
# (index into JAX_EVENTS, t_end, duration_ns); tracing runs on worker threads.
_JAX_ROWS = MTStageRing(1 << 12, width=3)
# (generation, t_start, duration_ns).  One writer at a time without a lock:
# the collector never runs two passes at once, and its callback must not
# take a lock that the thread it interrupted may hold.
_GC_ROWS = StageRing(1 << 12, width=3)
_gc_start = [0]

# The view change, step by step (core/timeout.py and
# core/message_handling.py write them, on the view-change path alone: a
# window without a view change writes none).
VIEWCHANGE_STAGES: Tuple[str, ...] = (
    "demand",  # the replica sent its own REQ-VIEW-CHANGE for the view
    "started",  # f+1 demands gathered: it emits its VIEW-CHANGE
    "new_view_sent",  # the view's primary holds n-f VIEW-CHANGEs: NEW-VIEW
    "entered",  # the NEW-VIEW applied: the replica stands in the view
)
VC_DEMAND = 0
VC_STARTED = 1
VC_NEW_VIEW_SENT = 2
VC_ENTERED = 3
# (replica, new_view, stage, t): a few rows a replica and view change.
# Locked: the replicas of one process may run on several loops.
_VIEWCHANGE_ROWS = MTStageRing(1 << 10)
# (replica, new_view, items, t): one row a validation of a VIEW-CHANGE or
# NEW-VIEW, items = the certificate checks it handed to the authenticator.
_VIEWCHANGE_ITEMS = MTStageRing(1 << 12)


def register_engine(engine) -> int:
    """Enter ``engine`` (anything with ``dispatch_rows()``) into the
    process timeline -> its id in dispatch rows.  Also where the
    collector's clock is installed: with the first engine."""
    install_collector_clock()
    engine_id = next(_ENGINE_IDS)
    _ENGINES[engine_id] = engine
    return engine_id


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        _gc_start[0] = time.monotonic_ns()
        return
    dur = time.monotonic_ns() - _gc_start[0]
    if info["generation"] == 2 or dur >= _MIN_NS:
        _GC_ROWS.push_row((info["generation"], _gc_start[0], dur))


def install_collector_clock() -> None:
    """Time the collector's passes from inside the program: one
    ``gc.callbacks`` entry a process, installed where the first engine
    or replica starts.  It changes nothing the collector does."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)


def note_jax_event(event: str, duration_secs: float, **_kwargs) -> None:
    """The ``jax.monitoring`` duration listener (utils/jaxcache.py
    registers it): jaxpr tracing, lowering, backend compile, cache
    retrieval."""
    i = _JAX_EVENT_IDS.get(event)
    duration_ns = int(duration_secs * 1e9)
    if i is not None and duration_ns >= _MIN_NS:
        _JAX_ROWS.push_row((i, time.monotonic_ns(), duration_ns))


def note_client_start(client_id: int, seq: int) -> None:
    """A client issued request ``seq`` (its first transmission)."""
    _CLIENT_ROWS.push(client_id, seq, C_START, time.monotonic_ns())


def note_viewchange(replica_id: int, new_view: int, stage: int) -> None:
    """Replica ``replica_id`` reached ``stage`` (:data:`VIEWCHANGE_STAGES`)
    of its change to ``new_view``."""
    _VIEWCHANGE_ROWS.push(replica_id, new_view, stage, time.monotonic_ns())


def note_viewchange_items(replica_id: int, new_view: int, items: int) -> None:
    """A validator of replica ``replica_id`` handed ``items`` certificate
    checks of the change to ``new_view`` to the authenticator."""
    _VIEWCHANGE_ITEMS.push(replica_id, new_view, items, time.monotonic_ns())


def timeline() -> dict:
    """Everything this process has on its timeline, as plain lists of
    rows whose instants are ``time.monotonic_ns()``, each with its ring's
    ``dropped`` count (rows overwritten):

    - ``dispatch``: per live engine ``{"engine", "rows", "dropped"}``,
      rows as :data:`DISPATCH_COLUMNS` with names decoded;
    - ``gc``: rows ``(generation, t_start, duration_ns)``, every
      generation-2 pass and any pass of 1 ms or more; beside them the
      collector's policy as it is now: ``frozen`` (objects in the permanent
      generation, which no pass walks) and ``thresholds``
      (placement.settle_collector sets both once warm-up ends);
    - ``jax``: rows ``(event, t_end, duration_ns)``, every event of
      :data:`JAX_EVENTS` that took 1 ms or more; beside them
      ``kernel_store``, per kernel what its executable cost this process
      (utils/kernelstore.py ``KernelStoreStats``): ``loads``, ``builds``,
      of them ``off_main_loads`` / ``off_main_builds`` (made off the
      main thread), ``load_failures``, ``load_s`` and inside it ``read_s``,
      ``deserialize_s`` and ``digest_s``, ``build_s``, ``bytes``;
    - ``client``: rows ``(client_id, seq, "start", t)``, one a request;
    - ``loops``: obs/looplag.py's idle clocks, one per live loop;
    - ``reply_checks``: the clients' reply checks summed over the process
      (utils/replycheck.py ``ReplyCheckStats``): native batches, checks,
      how many of them off the interpreter lock and how many inline,
      quorums formed, checks a write;
    - ``viewchange``: rows ``(replica, new_view, stage, t)``, one a step
      of :data:`VIEWCHANGE_STAGES` (none in a process that changed no
      view); beside them ``verify_items``, rows ``(replica, new_view,
      items, t)``, one a validation of the change's VIEW-CHANGEs and
      NEW-VIEW by that replica (``items``: the certificate checks it
      handed to the authenticator, that is to the verification engine
      where there is one; a certificate already checked is not checked
      again), and ``verify_dropped``.
    """
    from ..utils import kernelstore, replycheck
    from . import looplag

    dispatch = []
    for engine_id, engine in sorted(dict(_ENGINES).items()):
        rows, dropped = engine.dispatch_rows()
        dispatch.append({"engine": engine_id, "rows": rows, "dropped": dropped})
    gc_rows, gc_dropped = _GC_ROWS.read()
    jax_rows, jax_dropped = _JAX_ROWS.read()
    client_rows, client_dropped = _CLIENT_ROWS.read()
    vc_rows, vc_dropped = _VIEWCHANGE_ROWS.read()
    item_rows, items_dropped = _VIEWCHANGE_ITEMS.read()
    return {
        "dispatch_columns": list(DISPATCH_COLUMNS),
        "dispatch": dispatch,
        "gc": {
            "rows": gc_rows,
            "dropped": gc_dropped,
            "frozen": gc.get_freeze_count(),
            "thresholds": list(gc.get_threshold()),
        },
        "jax": {
            "rows": [(JAX_EVENTS[i], t, d) for i, t, d in jax_rows],
            "dropped": jax_dropped,
            "kernel_store": kernelstore.stats(),
        },
        "client": {
            "rows": [(c, s, CLIENT_STAGES[st], t) for c, s, st, t in client_rows],
            "dropped": client_dropped,
        },
        "loops": looplag.idle_clocks(),
        "reply_checks": replycheck.TOTAL.to_dict(),
        "viewchange": {
            "rows": [(r, v, VIEWCHANGE_STAGES[st], t) for r, v, st, t in vc_rows],
            "dropped": vc_dropped,
            "verify_items": item_rows,
            "verify_dropped": items_dropped,
        },
    }


# ---------------------------------------------------------------------------
# JSON trace dumps (MINBFT_TRACE_DUMP=path) and the bench stage table.


def clock_domain() -> str:
    """Identity of this process's monotonic-clock domain, stamped into
    every dump: ``time.monotonic`` reads the system-wide boot-relative
    CLOCK_MONOTONIC, so EVERY process on one host (one boot) shares the
    epoch — dumps with equal domains merge with zero offset and zero
    uncertainty, and only genuinely cross-host dumps pay the
    Cristian-style estimation (obs/clockalign.py).  Containers with
    private hostnames conservatively fall into separate domains even
    when the kernel clock is shared — estimation is the safe default,
    exactness the proven special case."""
    import socket

    return socket.gethostname()


def dump_path_for(
    kind: str,
    ident: int,
    base: Optional[str] = None,
    group: Optional[int] = None,
) -> Optional[str]:
    """Per-process-safe dump path: ``{base}.{r|c}{id}.json`` (multiple
    replicas/clients — in one process or many — never clobber).  Grouped
    recorders append ``g{group}``: a GroupRuntime's G cores share one
    replica id, so the group must be part of the filename or the cores'
    dumps clobber each other."""
    base = base if base is not None else os.environ.get(TRACE_DUMP_ENV)
    if not base:
        return None
    tag = {"replica": "r", "client": "c"}.get(kind, kind)
    gtag = "" if group is None else f"g{group}"
    return f"{base}.{tag}{ident}{gtag}.json"


def dump_recorder(rec: FlightRecorder, base: Optional[str] = None,
                  extra: Optional[dict] = None) -> Optional[str]:
    """Write one recorder's dump; returns the path (None when the dump
    env/base is unset — the recorder may be enabled for live scraping
    only)."""
    path = dump_path_for(rec.kind, rec.ident, base, group=rec.group)
    if path is None:
        return None
    doc = rec.to_dict()
    # Incarnation attribution (ISSUE 14): every dump says which process
    # produced it, so cross-node mergers can refuse to splice a restarted
    # replica onto its predecessor's timeline (obs/critpath.py) and a
    # merged artifact's numbers stay traceable to concrete pids/revs.
    # ``extra`` may override (tests construct synthetic incarnations).
    from . import runinfo

    doc.setdefault("run_id", runinfo.RUN_ID)
    doc.setdefault("build", runinfo.build_info())
    if extra:
        doc.update(extra)
    # noqa: AH102 - one-shot crash/shutdown dump; forensics cannot rely on executors
    with open(path, "w") as fh:
        json.dump(doc, fh)
    return path


def load_dumps(base: str) -> List[dict]:
    """Load every ``{base}.*.json`` trace dump (bench ingestion)."""
    import glob

    docs = []
    for path in sorted(glob.glob(base + ".*.json")):
        try:
            # noqa: AH102 - one-shot ingestion at bench report time
            with open(path) as fh:
                docs.append(json.load(fh))
        except (OSError, ValueError):
            continue
    return docs


def filter_group(docs: Iterable[dict], group: Optional[int]) -> List[dict]:
    """Restrict a dump set to one consensus group: docs stamped with a
    DIFFERENT group are dropped; unstamped docs (ungrouped recorders,
    shared engine docs, clients without a group label) are kept — the
    engine queues really are shared across groups, so excluding their
    doc would just lose the queue-wait attribution.  ``group=None`` is
    the identity."""
    docs = list(docs)
    if group is None:
        return docs
    return [d for d in docs if d.get("group") in (None, group)]


def merged_stage_hists(docs: Iterable[dict]) -> Dict[str, Log2Histogram]:
    """Merge dumped stage histograms across recorders.  Client stages
    are namespaced (``client_sign``...) so the one table carries both
    sides without key collisions; replica stages keep their bare names."""
    out: Dict[str, Log2Histogram] = {}
    for doc in docs:
        prefix = "client_" if doc.get("kind") == "client" else ""
        for name, hd in (doc.get("hists") or {}).items():
            h = Log2Histogram.from_dict(hd)
            key = prefix + name
            if key in out:
                out[key].merge(h)
            else:
                out[key] = h
    return out


def stage_table(
    docs: Iterable[dict], prefix: str, group: Optional[int] = None
) -> dict:
    """The bench's per-stage cost-breakdown keys:

    - ``{prefix}_stage_{name}_p50_ms`` — median time from the previous
      capture point to ``name`` (merged across every dumped recorder);
    - ``{prefix}_stage_{name}_share`` — that stage's fraction of the
      total replica-side recorded time (client stages overlap the
      replica pipeline by construction, so shares are computed over the
      replica stages only — they sum to 1.0).

    ``group`` restricts the table to one consensus group's recorders
    (see :func:`filter_group`) — the multi-group runtime dumps every
    core into one dump set.

    Returns {} when no dump carries histogram data, so a tracing-disabled
    bench emits byte-identical keys to a tracing-absent one.
    """
    hists = merged_stage_hists(filter_group(docs, group))
    if not hists:
        return {}
    out: dict = {}
    replica_total = sum(
        h.total_s for n, h in hists.items() if not n.startswith("client_")
    )
    for name, h in sorted(hists.items()):
        out[f"{prefix}_stage_{name}_p50_ms"] = round(h.percentile(50) * 1e3, 3)
        if not name.startswith("client_") and replica_total > 0:
            out[f"{prefix}_stage_{name}_share"] = round(
                h.total_s / replica_total, 4
            )
    return out
