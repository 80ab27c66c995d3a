"""Bench regression gate: compare a candidate bench artifact against a
committed baseline using the ``_runs``/``_mean``/``_stddev`` triples the
bench harness emits (PR 4 added them for variance hygiene; this tool is
their first consumer).

Scope — deliberately narrow and honest:

- Gated keys are EXACTLY the ``*_req_per_sec_mean`` triples present in
  BOTH artifacts (the committed-throughput headlines; kernel rates have
  no stddev companion and single-run phases carry stddev 0.0, which the
  relative noise floor below absorbs), plus the
  ``*_util_effective_per_sec`` utilization headlines (ISSUE 14: the
  ledger's effective useful-lane rate — no stddev companion, so the
  relative floor is the whole noise defense there), plus the open-loop
  curve headlines (ISSUE 15): ``load_*_goodput_per_sec`` gated on DROP
  like a throughput mean, and ``load_*_p99_ms`` gated on INCREASE — a
  latency key regresses when the candidate climbs past the allowance,
  with its own (wider) relative floor because single-seed tail latency
  swings far more than committed throughput does.  The (G, chips) grid's
  embedded per-point curves (``groups{G}x{C}_load_*``, ISSUE 17) join
  the same two rules, and its pool-aggregate
  ``groups{G}x{C}_util_effective_per_sec`` rides the utilization rule.
  The crash-recovery soak (ISSUE 20) adds two EXACT keys:
  ``chaos_recovery_time_ms`` gates on INCREASE with the latency floor
  (the recovery-time SLO — kill-to-first-executed wall time), and
  ``chaos_recovery_goodput_per_sec`` (whole-run goodput INCLUDING the
  outage window) gates on DROP like any throughput headline.  Exact
  matches, so no unrelated future ``*_time_ms`` key leaks into the gate.
- A key regresses when its drop exceeds BOTH noise defenses:
  ``drop > max(sigmas * sqrt(base_std² + cand_std²),
  rel_floor * base_mean)`` — the stddev band covers measured run-to-run
  variance, the relative floor covers the 1-core bench host's
  documented ±30% single-run swing (the round-5 profile, removed in PR 21) when runs=1
  makes the stddev lie at 0.
- Backend honesty is a HARD refusal, not a threshold: a
  ``tpu_unavailable`` (CPU-fallback) artifact can gate only against a
  CPU baseline and vice versa — comparing CPU throughput against chip
  throughput is not a regression check, it is a category error (the
  standing VERDICT r5 caution).  Nested ``last_tpu`` carry-forward
  blocks are never read: second-hand numbers gate nothing.

Exit codes (``python -m tools.benchgate``): 0 pass, 1 regression,
2 refusal/usage error — CI treats each differently (a refusal in CI is
a wiring bug, not a perf regression).
"""

from __future__ import annotations

import dataclasses
import json
import math
import re
from typing import Dict, List, Tuple

DEFAULT_SIGMAS = 3.0
DEFAULT_REL_FLOOR = 0.30
# Tail latency tolerance: p99 on the 1-core bench host legitimately
# doubles run-to-run (retransmit-ladder alignment, GC pauses), so the
# latency floor is deliberately wide — it catches order-of-magnitude
# wedges, not jitter.
DEFAULT_LAT_REL_FLOOR = 1.5

_MEAN_SUFFIX = "_req_per_sec_mean"
_STD_SUFFIX = "_req_per_sec_stddev"
# Utilization headline (ISSUE 14): gated like a mean triple whose stddev
# is 0.0 everywhere — the rel_floor absorbs single-window noise.
_UTIL_SUFFIX = "_util_effective_per_sec"
# Open-loop curve headlines (ISSUE 15).  Goodput gates on drop like any
# throughput key; p99 gates on INCREASE (lower is better).  Both are
# restricted to the load namespaces so unrelated future keys ending
# in ``_per_sec`` / ``_ms`` don't silently join the gate: the top-level
# ``load_*`` curve, plus the (G, chips) grid's embedded per-point curves
# ``groups{G}x{C}_load_*`` (ISSUE 17 — the pattern is anchored, so a
# plain ``groups{G}_*`` sweep key can never match it).
_LOAD_PREFIX = "load_"
_GRID_LOAD_RE = re.compile(r"^groups\d+x\d+_load_")
_LOAD_GOODPUT_SUFFIX = "_goodput_per_sec"
_LOAD_P99_SUFFIX = "_p99_ms"
# SLO finality headline (perf/SLO.md): scheduled-origin finality p99
# with unresolved requests charged their age-so-far.  Gated on INCREASE
# like the plain p99 (and matched FIRST — it also ends in "_p99_ms").
_LOAD_FINALITY_SUFFIX = "_finality_p99_ms"
# Crash-recovery soak headlines (ISSUE 20, perf/CHAOS.md §recovery):
# EXACT key matches, not suffix rules — the recovery phase emits exactly
# these two, and an exact match can never pull an unrelated future
# ``*_time_ms`` key into the gate.  Recovery time gates on INCREASE with
# the latency floor (kill-to-first-executed wall time is single-run and
# jittery); under-recovery goodput gates on DROP like any throughput.
_RECOVERY_TIME_KEY = "chaos_recovery_time_ms"
_RECOVERY_GOODPUT_KEY = "chaos_recovery_goodput_per_sec"


def _in_load_namespace(key: str) -> bool:
    return key.startswith(_LOAD_PREFIX) or bool(_GRID_LOAD_RE.match(key))


class BackendMismatch(Exception):
    """Candidate and baseline artifacts ran on different backend kinds —
    the comparison is refused, never softened into a threshold."""


@dataclasses.dataclass
class KeyResult:
    key: str  # the config prefix (e.g. "e2e", "mptcp")
    baseline: float
    candidate: float
    drop: float  # signed regression amount (positive = worse): baseline
    # - candidate for throughput keys, candidate - baseline for latency
    allowed: float  # the noise allowance the drop is judged against
    status: str  # "ok" | "regression" | "improved"
    direction: str = "drop"  # "drop" (lower cand = worse) | "increase"


@dataclasses.dataclass
class GateReport:
    results: List[KeyResult]
    missing: List[str]  # gated keys in the baseline absent from candidate
    backend_kind: str

    @property
    def regressions(self) -> List[KeyResult]:
        return [r for r in self.results if r.status == "regression"]

    @property
    def ok(self) -> bool:
        return not self.regressions


def load_artifact(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def backend_kind(artifact: dict) -> str:
    """The honesty class of an artifact: ``cpu-fallback`` when stamped
    ``tpu_unavailable`` (regardless of what its carried-forward blocks
    say), else the recorded backend."""
    if artifact.get("tpu_unavailable"):
        return "cpu-fallback"
    return str(artifact.get("backend", "unknown"))


def gated_pairs(
    baseline: dict, candidate: dict
) -> Tuple[Dict[str, Tuple[str, str]], List[str]]:
    """``{prefix: (key, direction)}`` for every gated key present in
    both artifacts, plus the prefixes the candidate dropped.
    ``direction`` is ``"drop"`` (regression = candidate fell) or
    ``"increase"`` (regression = candidate climbed; latency keys)."""
    pairs: Dict[str, Tuple[str, str]] = {}
    missing: List[str] = []
    for key in sorted(baseline):
        direction = "drop"
        if key.endswith(_MEAN_SUFFIX):
            prefix = key[: -len(_MEAN_SUFFIX)]
        elif key.endswith(_UTIL_SUFFIX):
            # report label "{config}_util"; the stddev lookup in
            # compare() then misses by construction and reads 0.0 —
            # exactly the single-run semantics the rel_floor covers
            prefix = key[: -len(_UTIL_SUFFIX)] + "_util"
        elif _in_load_namespace(key) and key.endswith(
            _LOAD_GOODPUT_SUFFIX
        ):
            prefix = key[: -len("_per_sec")]
        elif _in_load_namespace(key) and key.endswith(
            _LOAD_FINALITY_SUFFIX
        ):
            prefix = key[: -len("_ms")]
            direction = "increase"
        elif _in_load_namespace(key) and key.endswith(
            _LOAD_P99_SUFFIX
        ):
            prefix = key[: -len("_ms")]
            direction = "increase"
        elif key == _RECOVERY_TIME_KEY:
            prefix = key[: -len("_ms")]
            direction = "increase"
        elif key == _RECOVERY_GOODPUT_KEY:
            prefix = key[: -len("_per_sec")]
        else:
            continue
        if key in candidate:
            pairs[prefix] = (key, direction)
        else:
            missing.append(prefix)
    return pairs, missing


def compare(
    baseline: dict,
    candidate: dict,
    sigmas: float = DEFAULT_SIGMAS,
    rel_floor: float = DEFAULT_REL_FLOOR,
    lat_rel_floor: float = DEFAULT_LAT_REL_FLOOR,
) -> GateReport:
    """Gate ``candidate`` against ``baseline``.  Raises
    :class:`BackendMismatch` before reading a single number when the
    artifacts' backend kinds differ."""
    bk, ck = backend_kind(baseline), backend_kind(candidate)
    if bk != ck:
        raise BackendMismatch(
            f"baseline is {bk!r} but candidate is {ck!r}: CPU artifacts "
            "gate only against CPU baselines (tpu_unavailable caution); "
            "re-baseline on the candidate's backend instead"
        )
    pairs, missing = gated_pairs(baseline, candidate)
    results: List[KeyResult] = []
    for prefix, (mean_key, direction) in pairs.items():
        base_mean = float(baseline[mean_key])
        cand_mean = float(candidate[mean_key])
        base_std = float(baseline.get(prefix + _STD_SUFFIX, 0.0))
        cand_std = float(candidate.get(prefix + _STD_SUFFIX, 0.0))
        if direction == "increase":
            drop = cand_mean - base_mean
            floor = lat_rel_floor
        else:
            drop = base_mean - cand_mean
            floor = rel_floor
        allowed = max(
            sigmas * math.sqrt(base_std**2 + cand_std**2),
            floor * base_mean,
        )
        if drop > allowed:
            status = "regression"
        elif drop < 0:
            status = "improved"
        else:
            status = "ok"
        results.append(
            KeyResult(
                key=prefix,
                baseline=base_mean,
                candidate=cand_mean,
                drop=drop,
                allowed=allowed,
                status=status,
                direction=direction,
            )
        )
    return GateReport(results=results, missing=missing, backend_kind=ck)
