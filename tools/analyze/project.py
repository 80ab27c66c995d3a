"""Project wiring for the analysis passes.

Everything repo-specific lives HERE (and in the committed baseline), not
in the passes: the passes implement reusable checks, this module tells
them which files, classes, locks, and message kinds this codebase cares
about.  Tests build their own config objects pointed at fixture trees.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

# ---------------------------------------------------------------------------
# lock discipline


@dataclasses.dataclass(frozen=True)
class LockClassSpec:
    """One state class under lock discipline.

    ``mode``:

    - ``"threads"`` — real preemptive concurrency (worker threads touch the
      attributes): EVERY write to a guarded attribute outside ``__init__``
      must be inside ``with <lock>``.
    - ``"loop"`` — asyncio event-loop confined: writes in sync methods (or
      async methods with no suspension point) are loop-atomic and allowed;
      writes in an async method that CAN suspend must hold the lock — a
      mutation racing an ``await`` is exactly the interleaving hazard the
      reference's race-detector tier exists to catch.

    ``guarded`` entries are dotted attribute paths relative to ``self``
    (subscripts are wildcards): ``"_next_cv"``, ``"_queues.stats"``.  The
    special value ``"auto"`` infers the guarded set: every attribute path
    the class itself writes under one of its locks somewhere (lock-affinity
    inference — if the code bothers to lock an attribute once, unlocked
    writes elsewhere are suspect).
    """

    path: str
    cls: str
    locks: Tuple[str, ...]
    guarded: Tuple[str, ...] = ("auto",)
    mode: str = "loop"


# ---------------------------------------------------------------------------
# trace purity


@dataclasses.dataclass(frozen=True)
class TracePurityConfig:
    """Where jitted code lives and what marks a function as a trace root."""

    roots: Tuple[str, ...] = ()
    # Call wrappers whose function-valued arguments become traced code.
    jit_wrappers: Tuple[str, ...] = (
        "jax.jit",
        "jit",
        "per_mode_jit",
        "jax.vmap",
        "vmap",
        "jax.pmap",
        "shard_map",
        "jax.lax.scan",
        "lax.scan",
        "jax.lax.fori_loop",
        "lax.fori_loop",
        "jax.lax.while_loop",
        "lax.while_loop",
        "jax.lax.cond",
        "lax.cond",
        "jax.checkpoint",
        "jax.remat",
    )
    # Annotation names that mark a parameter as a host-static Python value
    # (never a tracer): branching on it and np.* over it are trace-time
    # constant folding, not impurity.
    static_types: Tuple[str, ...] = ("int", "float", "bool", "str", "bytes")
    # (module-relative path, function name) -> parameter names that are
    # static Python values at trace time (branching on them is fine).
    static_params: Dict[Tuple[str, str], Tuple[str, ...]] = dataclasses.field(
        default_factory=dict
    )


# ---------------------------------------------------------------------------
# handler / codec exhaustiveness


@dataclasses.dataclass(frozen=True)
class ExhaustivenessConfig:
    message_module: str = "minbft_tpu/messages/message.py"
    codec_module: str = "minbft_tpu/messages/codec.py"
    authen_module: str = "minbft_tpu/messages/authen.py"
    handler_module: str = "minbft_tpu/core/message_handling.py"
    # Dispatch functions every wire-processable kind must appear in
    # (directly or via a classification tuple like CERTIFIED_MESSAGES).
    handler_functions: Tuple[str, ...] = ("validate_message", "process_message")
    # kind -> (module that MUST handle it instead, reason).  The pass
    # verifies the alternative module really isinstance-checks the kind —
    # an exemption that stops being true becomes a finding again.
    handler_alternatives: Dict[str, Tuple[str, str]] = dataclasses.field(
        default_factory=dict
    )
    # kind -> reason it legitimately has no authen-bytes rule.
    authen_exempt: Dict[str, str] = dataclasses.field(default_factory=dict)


# ---------------------------------------------------------------------------
# secret hygiene


@dataclasses.dataclass(frozen=True)
class SecretHygieneConfig:
    """Name-taint rules for key material.

    An identifier is secret-tainted when ``secret_re`` matches one of its
    underscore-separated words and ``public_re`` does not.  The word split
    keeps "keyspec"/"monkey" out while catching "key", "priv", "seed".
    """

    roots: Tuple[str, ...] = ()
    secret_re: str = (
        r"^(priv|private|privkey|secret|secrets|sealed|seed|scalar|sk|mk|"
        r"master|key|keys|mackey|passphrase|password)$"
    )
    public_re: str = (
        r"^(pub|public|keyspec|keystore|keytool|id|ids|kid|anchor|anchors|"
        r"fingerprint|digest|spec|store|error|file|path|len|size|env|"
        # A chaos-replay seed is a PUBLIC token: the fault-injection
        # layer prints it on failure so the run can be reproduced
        # (testing/faultnet.py) — it is an RNG schedule id, not key
        # material, and identifiers carry the "chaos" word to say so.
        r"chaos)$"
    )


# ---------------------------------------------------------------------------
# dead code (the pyflakes floor for bare images)


@dataclasses.dataclass(frozen=True)
class DeadCodeConfig:
    roots: Tuple[str, ...] = ()
    # ``from x import y`` in an __init__.py is the re-export idiom; only
    # flag unused imports there when the module defines __all__ and the
    # name is not listed.
    init_reexports_ok: bool = True


# ---------------------------------------------------------------------------
# async hygiene (AH)


@dataclasses.dataclass(frozen=True)
class AsyncHygieneConfig:
    """Event-loop blocking-sink rules for the coroutine call graph.

    The pass roots a cross-module call graph at every ``async def`` under
    ``roots`` and follows *calls* (sync helpers run inline on the loop;
    un-awaited coroutine calls still run on the loop via create_task).
    Functions passed by REFERENCE to ``asyncio.to_thread`` /
    ``run_in_executor`` never enter the graph — the hand-off itself is
    the suspension-aware boundary, so blocking work behind it is free.

    ``boundary`` lists additional ``"relpath::qualname"`` functions the
    walk must not descend into (justified engine hand-off points whose
    blocking is micro-bounded by design); each entry carries a reason.
    """

    roots: Tuple[str, ...] = ()
    # Dotted call origins that block the loop outright (AH101).
    blocking_calls: Tuple[str, ...] = (
        "time.sleep",
        "os.system",
        "os.wait",
        "os.waitpid",
        "subprocess.run",
        "subprocess.call",
        "subprocess.check_call",
        "subprocess.check_output",
        "socket.create_connection",
        "socket.getaddrinfo",
        "urllib.request.urlopen",
        "requests.get",
        "requests.post",
    )
    # Sync file-IO sinks (AH102): the builtin plus Path-style methods.
    io_calls: Tuple[str, ...] = ("open",)
    io_methods: Tuple[str, ...] = (
        "read_text",
        "write_text",
        "read_bytes",
        "write_bytes",
    )
    # Attribute-call / with-statement lock heuristics (AH103): a sync
    # ``.acquire()`` or ``with self._lock`` on the loop serializes the
    # loop behind whatever thread holds the lock.
    lock_attr_re: str = r"(^|_)(lock|cond|condition|sema|semaphore)s?$"
    # (relpath::qualname, reason) — boundary functions the walk skips.
    boundary: Dict[str, str] = dataclasses.field(default_factory=dict)


# ---------------------------------------------------------------------------
# task lifecycle (TL)


@dataclasses.dataclass(frozen=True)
class TaskLifecycleConfig:
    """Rules for background-task retention (the ``_bg_tasks`` contract).

    A task whose only reference is the scheduler's weak set can be
    garbage-collected mid-flight and its exception silently dropped —
    the exact bug fixed twice before this pass existed (PR 2, PR 6).
    ``roots`` are the files/dirs scanned; ``factories`` the call names
    that mint tasks.
    """

    roots: Tuple[str, ...] = ()
    factories: Tuple[str, ...] = ("create_task", "ensure_future")
    # Container-mutator names that count as retention when the task is
    # their argument (self._bg_tasks.add(task), tasks.append(task), …).
    retainers: Tuple[str, ...] = ("add", "append", "insert", "setdefault")


# ---------------------------------------------------------------------------
# schema drift (SD)


@dataclasses.dataclass(frozen=True)
class SchemaDriftConfig:
    """What the SD pass cross-checks: ``minbft_*`` names pinned in tests
    must match a Prometheus family registered by the prom module
    (pinned-but-unregistered, SD705)."""

    prom_module: str = "minbft_tpu/obs/prom.py"
    # Test files whose string literals pin prom names.
    pinned_tests: Tuple[str, ...] = ()


# ---------------------------------------------------------------------------
# env registry (ER)


@dataclasses.dataclass(frozen=True)
class EnvRegistryConfig:
    """Registry contract for environment knobs.

    Every ``MINBFT_*``/``CONSENSUS_*`` string literal at a getenv site in
    ``roots`` must appear in the committed registry markdown with a
    one-line description; registry entries matching no live site are
    dead.  F-string env names contribute prefix wildcards
    (``f"MINBFT_FOO_{x}"`` -> ``MINBFT_FOO_*``) that keep their
    expansions alive.
    """

    roots: Tuple[str, ...] = ()
    registry: str = "tools/analyze/ENV_VARS.md"
    name_re: str = r"^(MINBFT|CONSENSUS)_[A-Z0-9_]+$"
    prefix_re: str = r"^(MINBFT|CONSENSUS)_[A-Z0-9_]*$"


# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AnalyzeConfig:
    source_roots: Tuple[str, ...]
    lock_classes: Tuple[LockClassSpec, ...]
    trace: TracePurityConfig
    exhaustiveness: Optional[ExhaustivenessConfig]
    secrets: SecretHygieneConfig
    dead: DeadCodeConfig
    # v2 passes (ISSUE 16); None disables the pass, so fixture configs
    # that predate it keep working unchanged.
    async_hygiene: Optional[AsyncHygieneConfig] = None
    tasks: Optional[TaskLifecycleConfig] = None
    schema: Optional[SchemaDriftConfig] = None
    env: Optional[EnvRegistryConfig] = None


def default_config() -> AnalyzeConfig:
    """The wiring for THIS repository."""
    return AnalyzeConfig(
        source_roots=(
            "minbft_tpu",
            "tests",
            "tools/analyze",
            "__graft_entry__.py",
        ),
        lock_classes=(
            # Replica-internal state machines (ISSUE: the reference's
            # `go test -race` tier).  All are event-loop confined; their
            # condvars/locks protect state mutated across awaits.
            LockClassSpec(
                path="minbft_tpu/core/internal/clientstate.py",
                cls="ClientState",
                locks=("_cond",),
            ),
            LockClassSpec(
                path="minbft_tpu/core/internal/peerstate.py",
                cls="PeerState",
                locks=("_cond",),
            ),
            LockClassSpec(
                path="minbft_tpu/core/internal/viewstate.py",
                cls="ViewState",
                locks=("_write_lock",),
                guarded=("_current",),
            ),
            LockClassSpec(
                path="minbft_tpu/core/internal/messagelog.py",
                cls="MessageLog",
                locks=(),
                guarded=("_entries", "_seq0", "_waiters"),
            ),
            LockClassSpec(
                path="minbft_tpu/core/internal/requestlist.py",
                cls="RequestList",
                locks=(),
                guarded=("_by_client",),
            ),
            # Bundle-ingest runtime (ISSUE 6): one pump + one tick task
            # per stream share the rx queue and the pump's EOF flag —
            # loop-confined, so the suspension-aware mode flags any
            # mutation racing an await without a lock.
            LockClassSpec(
                path="minbft_tpu/core/message_handling.py",
                cls="_BundleIngestor",
                locks=(),
                guarded=("_rx", "_eof_pending"),
            ),
            # Tick accounting the ingest path feeds from the event loop;
            # the Prometheus scrape thread only READS (GIL-atomic ints,
            # the documented monitoring contract).
            LockClassSpec(
                path="minbft_tpu/utils/metrics.py",
                cls="ReplicaMetrics",
                locks=(),
                # loop_lag: written only by the replica's LoopLagSampler
                # task (obs/looplag.py) on the owning loop; scrape
                # threads read GIL-atomic ints.
                guarded=("counters", "ingest_hist", "loop_lag"),
            ),
            # The batching engine is the one place real threads touch
            # shared state (dispatchers run via asyncio.to_thread):
            # kernel memo and cross-thread stats need their locks held on
            # every write.
            LockClassSpec(
                path="minbft_tpu/parallel/engine.py",
                cls="BatchVerifier",
                locks=("_sharded_lock", "_stats_lock"),
                # EXPLICIT, not "auto": inference learns guards from
                # locked writes, so deleting every `with self._stats_lock`
                # at once would silently un-guard the attribute.  These
                # pin the kernel memo and the cross-thread dispatcher
                # stats accounting (padded_lanes: the round-1 race fix;
                # host_prep_time_s: the round-6 prep/device split)
                # regardless of what the code currently locks.
                guarded=(
                    "_sharded_kernels",
                    "_queues.stats.padded_lanes",
                    "_queues.stats.host_prep_time_s",
                    # The sign queues' dispatcher-side stats follow the
                    # same rule: _note_sign_prep runs on max_inflight
                    # worker threads and must hold _stats_lock.
                    "_sign_queues.stats.padded_lanes",
                    "_sign_queues.stats.host_prep_time_s",
                    # Dispatch-record queue-name interning: lock-free
                    # read, locked insert (a queue is made on the loop;
                    # timeline() decodes from any thread).
                    "_obs_queue_ids",
                ),
                mode="threads",
            ),
            # The staging-buffer pool is checked out/returned from
            # max_inflight worker threads concurrently: its free-list
            # must only mutate under its lock.
            LockClassSpec(
                path="minbft_tpu/parallel/engine.py",
                cls="_StagingPool",
                locks=("_lock",),
                guarded=("_free",),
                mode="threads",
            ),
            LockClassSpec(
                path="minbft_tpu/parallel/engine.py",
                cls="_SchemeQueue",
                locks=(),
                guarded=("pending", "_memo", "_neg_memo", "_inflight_futs"),
            ),
            # The flush machinery shared by the verify and sign queues:
            # event-loop confined (dispatchers hop to threads via
            # asyncio.to_thread).  Only the batching state is guarded —
            # the write-off/probe counters are deliberately benign-racy
            # (a stale read costs one extra probe or fallback batch,
            # never correctness) and suspend-crossing writes to them are
            # part of the design, exactly as in the pre-split
            # _SchemeQueue.
            LockClassSpec(
                path="minbft_tpu/parallel/engine.py",
                cls="_DispatchQueue",
                locks=(),
                guarded=("pending", "inflight", "_flush_handle"),
            ),
            LockClassSpec(
                path="minbft_tpu/parallel/engine.py",
                cls="_SignQueue",
                locks=(),
                guarded=("pending",),
            ),
            # Multi-device engine pool (ISSUE 17): placement, facade
            # cache, in-flight counters, and the rolling attribution
            # ledgers are all event-loop confined BY CONTRACT — the pool
            # routes; the per-chip BatchVerifiers own all the real
            # thread crossings.  A suspend-crossing mutation here would
            # tear rebalance's in-flight check against a dispatch.
            LockClassSpec(
                path="minbft_tpu/parallel/pool.py",
                cls="EnginePool",
                locks=(),
                guarded=(
                    "_placement",
                    "_facades",
                    "_inflight",
                    "_util_ledgers",
                    "_ceilings",
                ),
            ),
            LockClassSpec(
                path="minbft_tpu/parallel/pool.py",
                cls="_GroupEngine",
                locks=(),
                guarded=("group",),
            ),
            # Flight-recorder rings (obs/trace.py, ISSUE 4).  StageRing
            # is SINGLE-writer by contract — only the owning event loop
            # pushes — so it is loop-confined with no lock; MTStageRing
            # subclasses it for the engine's worker threads, wrapping
            # push/snapshot in `with self._lock` (the storage writes
            # live in StageRing's sync bodies, serialized by the
            # subclass's lock wrappers — the same locked-writes
            # discipline as the engine stats; the multi-producer hammer
            # in tests/test_obs.py pins the torn-row invariant).
            LockClassSpec(
                path="minbft_tpu/obs/trace.py",
                cls="StageRing",
                locks=(),
                guarded=("_buf", "_idx", "_n", "_pushed"),
            ),
            LockClassSpec(
                path="minbft_tpu/obs/trace.py",
                cls="MTStageRing",
                locks=("_lock",),
                guarded=("_buf", "_idx", "_n", "_pushed"),
                mode="threads",
            ),
            # The recorder's pairing map is event-loop confined like the
            # ring it feeds (note() is sync — loop-atomic end to end).
            LockClassSpec(
                path="minbft_tpu/obs/trace.py",
                cls="FlightRecorder",
                locks=(),
                guarded=("_last",),
            ),
            # Telemetry rings (obs/timeseries.py, ISSUE 14): written by
            # samplers on the event loop AND read/merged from the scrape
            # thread, so every access to the slot maps goes through
            # `with self._lock` (the MTStageRing discipline; the
            # concurrent-writer hammer in tests/test_timeseries.py pins
            # the no-lost-update invariant).
            LockClassSpec(
                path="minbft_tpu/obs/timeseries.py",
                cls="TimeSeries",
                locks=("_lock",),
                guarded=("_series", "_kinds"),
                mode="threads",
            ),
            # Chaos fault fabric (testing/faultnet.py, ISSUE 5): ONE
            # FaultNet is shared by every wrapped endpoint's pipes on one
            # event loop.  Scripted-state flips (stall/partition/reset
            # epoch/plan swaps) and census bumps are sync methods —
            # loop-atomic; the async pipe() only READS shared state
            # between awaits, so a mutation appearing inside a
            # suspendable method would be exactly the torn-schedule race
            # this spec exists to catch.
            LockClassSpec(
                path="minbft_tpu/testing/faultnet.py",
                cls="FaultNet",
                locks=(),
                guarded=(
                    "_default_plan",
                    "_plans",
                    "_links",
                    "_stalled",
                    "_partition",
                    "_reset_epoch",
                    "_state_event",
                ),
            ),
            LockClassSpec(
                path="minbft_tpu/testing/faultnet.py",
                cls="FaultCensus",
                locks=(),
                guarded=("counters", "links", "frames"),
            ),
            # Multi-group shared transport (minbft_tpu/groups, ISSUE 10):
            # ONE _SharedChannel per destination is shared by G logical
            # group streams on one event loop — the per-group rx queue
            # registry, shared tx queue, and driver-task handle must
            # only mutate loop-atomically (the group-isolation contract:
            # a suspend-crossing mutation here could tear one group's
            # attach against another's EOF sweep).
            LockClassSpec(
                path="minbft_tpu/groups/runtime.py",
                cls="_SharedChannel",
                locks=(),
                guarded=("_tx", "_rx", "_driver", "_closed"),
            ),
            LockClassSpec(
                path="minbft_tpu/groups/runtime.py",
                cls="SharedChannelMux",
                locks=(),
                guarded=("_channels",),
            ),
            # The runtime's core list and the router's group map are
            # written once at construction and read by every stream
            # handler task afterwards — any later mutation racing an
            # await is a bug (groups cannot be added live; that is the
            # reconfiguration item on the roadmap, not an accident).
            LockClassSpec(
                path="minbft_tpu/groups/runtime.py",
                cls="GroupRuntime",
                locks=(),
                guarded=("cores", "n_groups"),
            ),
            LockClassSpec(
                path="minbft_tpu/groups/router.py",
                cls="ShardRouter",
                locks=(),
                guarded=("n_groups",),
            ),
            LockClassSpec(
                path="minbft_tpu/groups/router.py",
                cls="MultiGroupClient",
                locks=(),
                guarded=("_clients", "router"),
            ),
            # SLO budget ledgers (obs/slo.py, ISSUE 19): arrive/commit
            # run on the owning replica's event loop (sync bodies, so
            # loop-atomic); the scrape thread only reads GIL-atomic ints
            # — the StageRing single-writer discipline.
            LockClassSpec(
                path="minbft_tpu/obs/slo.py",
                cls="BudgetLedger",
                locks=(),
                guarded=(
                    "good",
                    "breached",
                    "breached_budget_ns",
                    "_origin",
                ),
            ),
            # The breach spool's counters are written only by the watch
            # task / loadgen runner on one loop; maybe_dump() is sync end
            # to end (the disk write is the suspension-free tail).
            LockClassSpec(
                path="minbft_tpu/obs/slo.py",
                cls="BreachSpool",
                locks=(),
                guarded=("written", "suppressed"),
            ),
            LockClassSpec(
                path="minbft_tpu/obs/slo.py",
                cls="TokenBucket",
                locks=(),
                guarded=("_tokens", "_t"),
            ),
            # The software USIG's counter is certified-then-incremented
            # under a real threading.Lock (reference ecallLock).
            LockClassSpec(
                path="minbft_tpu/usig/software.py",
                cls="_BaseUSIG",
                locks=("_lock",),
                guarded=("_counter",),
                mode="threads",
            ),
        ),
        trace=TracePurityConfig(
            # obs/ included (ISSUE 4): no flight-recorder hook may be
            # reachable from jitted code — the pass verifies obs/ holds
            # no jit roots and nothing traced calls into it.
            roots=("minbft_tpu/ops", "minbft_tpu/parallel", "minbft_tpu/obs"),
            # FieldSpec bundles host-static field constants (moduli,
            # Montgomery R^2, …) — see ops/limbs.py.
            static_types=("int", "float", "bool", "str", "bytes", "FieldSpec"),
        ),
        exhaustiveness=ExhaustivenessConfig(
            handler_alternatives={
                # HELLO is the transport handshake: consumed by the
                # connection-level hello handler in message_handling.py
                # before the replica dispatch ever sees it.
                "Hello": (
                    "minbft_tpu/core/message_handling.py",
                    "transport handshake (make_hello_handler)",
                ),
                # REPLY is client-bound: replicas emit it, only the client
                # validates/consumes it.
                "Reply": (
                    "minbft_tpu/client/client.py",
                    "client-side message (Client._handle_reply path)",
                ),
                # BUSY is client-bound like REPLY: replicas emit it at the
                # admission boundary, only the client consumes it.
                "Busy": (
                    "minbft_tpu/client/client.py",
                    "client-side admission signal (Client._handle_busy path)",
                ),
            },
            # No authen exemptions needed: LogBase — the one unsigned kind —
            # carries neither a signature nor a ui field, so the structural
            # rule already exempts it (its claim is the embedded
            # f+1-checkpoint certificate; see messages.message.LogBase).
            authen_exempt={},
        ),
        secrets=SecretHygieneConfig(
            roots=("minbft_tpu",),
        ),
        dead=DeadCodeConfig(
            roots=(
                "minbft_tpu",
                "tests",
                "tools/analyze",
                "__graft_entry__.py",
            ),
        ),
        async_hygiene=AsyncHygieneConfig(
            # Product code only: tests block freely (pytest-asyncio runs
            # each loop for one test).
            roots=("minbft_tpu",),
            boundary={},  # filled below once real boundary sites are known
        ),
        tasks=TaskLifecycleConfig(
            roots=("minbft_tpu",),
        ),
        schema=SchemaDriftConfig(
            prom_module="minbft_tpu/obs/prom.py",
            # Tests that pin PRODUCT prom families by literal name.
            # (test_metrics_endpoint.py pins only its own local fixture
            # families, so it is deliberately absent.)
            pinned_tests=(
                "tests/test_obs.py",
                "tests/test_chaos.py",
                "tests/test_process_cluster.py",
            ),
        ),
        env=EnvRegistryConfig(
            roots=("minbft_tpu", "__graft_entry__.py"),
        ),
    )
