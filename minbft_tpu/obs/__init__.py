"""Observability subsystem: the protocol flight recorder and the
cluster critical path.

Layers (ISSUEs 4 and 8; SURVEY.md §5 notes the reference's only
instrumentation is leveled logging):

- :mod:`~minbft_tpu.obs.trace` — per-request stage spans into
  preallocated ring buffers, with per-stage log2 histograms and the
  JSON trace dump (``MINBFT_TRACE_DUMP=path``) that ``load_dumps``,
  the critical-path merge and ``peer slo --dumps`` read;
- :mod:`~minbft_tpu.obs.hist` — fixed-bucket mergeable latency
  histograms (the streaming counterpart of the exact-but-unmergeable
  :class:`~minbft_tpu.utils.metrics.LatencyReservoir`), with negative
  durations counted, never silently clamped;
- :mod:`~minbft_tpu.obs.prom` — Prometheus text exposition served from
  an stdlib HTTP endpoint (``peer run --metrics-port`` / the
  ``peer metrics`` scrape subcommand, which can also merge several
  targets into one cluster aggregate);
- :mod:`~minbft_tpu.obs.clockalign` — NTP-free pairwise clock-offset
  estimation from the protocol's own matched send/recv span pairs;
- :mod:`~minbft_tpu.obs.critpath` — the cross-node trace merge: one
  causal timeline per committed request, with queue-wait and loop-lag
  attribution (perf/CRITICAL_PATH.md);
- :mod:`~minbft_tpu.obs.looplag` — event-loop scheduling-lag sampler
  (GIL/loop saturation as a first-class metric);
- :mod:`~minbft_tpu.obs.timeseries` — fixed-capacity per-interval
  counter-delta rings (the saturation timeline: shape-over-time, not
  just end-of-run means), mergeable like the histograms and dumped as
  ``{base}.ts.json`` next to the flight-recorder dumps;
- :mod:`~minbft_tpu.obs.ledger` — the device-utilization ledger: busy
  vs idle wall-seconds per engine queue, lanes classed useful /
  padding / memo-duplicate / host-fallback, and the multiplicative
  headroom decomposition against a calibrated per-backend ceiling;
- :mod:`~minbft_tpu.obs.runinfo` — per-incarnation ``RUN_ID`` and the
  ``minbft_build_info`` attribution block every dump and exposition
  carries;
- :mod:`~minbft_tpu.obs.slo` — the latency-SLO engine: per-request
  finality budgets classified at commit-quorum time, multi-window
  error-budget burn rates over the telemetry rings, critpath breach
  attribution, and the breach-triggered forensic auto-dump
  (perf/SLO.md).

Nothing in this package is reachable from jitted code (enforced by the
``tools/analyze`` trace-purity pass), and with tracing disabled the
protocol pays one predicated attribute check per hook.
"""

from .hist import Log2Histogram
from .ledger import Decomposition, DeviceLedger, QueueWindow
from .prom import (
    MetricsServer,
    collect_faultnet,
    collect_replica,
    render_families,
    scrape,
)
from .slo import (
    BreachSpool,
    BudgetLedger,
    SLOPolicy,
    breach_report,
    build_bundle,
    burn_rates,
    register_slo_series,
    slo_enabled,
)
from .timeseries import (
    CounterSampler,
    IncarnationMismatch,
    TimeSeries,
    dump_timeseries,
    merge_timeseries_docs,
)
from .trace import (
    CLIENT_STAGES,
    REPLICA_STAGES,
    FlightRecorder,
    MTStageRing,
    StageRing,
    dump_recorder,
    load_dumps,
    stage_table,
    tracing_enabled,
)

__all__ = [
    "CLIENT_STAGES",
    "REPLICA_STAGES",
    "BreachSpool",
    "BudgetLedger",
    "CounterSampler",
    "Decomposition",
    "DeviceLedger",
    "FlightRecorder",
    "IncarnationMismatch",
    "Log2Histogram",
    "MTStageRing",
    "MetricsServer",
    "QueueWindow",
    "SLOPolicy",
    "StageRing",
    "TimeSeries",
    "breach_report",
    "build_bundle",
    "burn_rates",
    "collect_faultnet",
    "collect_replica",
    "dump_recorder",
    "dump_timeseries",
    "load_dumps",
    "merge_timeseries_docs",
    "register_slo_series",
    "render_families",
    "scrape",
    "slo_enabled",
    "stage_table",
    "tracing_enabled",
]
