"""What a window leaves for the metrics to read, and the arithmetic that
turns it into numbers: percentiles, counter deltas.

The per-layer readers (``benchmark/layer_metrics/<name>.py``) get one
:class:`Observations` and nothing else.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence


def percentile(sorted_values: Sequence[float], q: float) -> float:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not sorted_values:
        raise ValueError("no values")
    pos = (len(sorted_values) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(sorted_values) - 1)
    return sorted_values[lo] + (sorted_values[hi] - sorted_values[lo]) * (pos - lo)


def log2_bucket_percentile(buckets: Sequence[int], q: float) -> Optional[float]:
    """Percentile in seconds of a log2-microsecond histogram (bucket i holds
    2**(i-1) < us <= 2**i), interpolated geometrically inside the bucket."""
    total = sum(buckets)
    if total <= 0:
        return None
    rank = total * q / 100.0
    seen = 0
    for i, c in enumerate(buckets):
        if c and seen + c >= rank:
            lo_us = 0.5 if i == 0 else float(1 << (i - 1))
            hi_us = float(1 << i)
            frac = (rank - seen) / c
            return lo_us * (hi_us / lo_us) ** frac / 1e6
        seen += c
    return None


@dataclasses.dataclass
class Observations:
    """One window, as the per-layer readers see it."""

    window_s: float
    latencies_ms: List[float]  # sorted; every write issued in the window that was answered
    commits: int  # writes acknowledged inside the window
    engine_deltas: List[dict]  # compare.counts_delta per engine, over the window
    device_kind: str
    platform: str
    kernels: Dict[str, object]  # name -> module of benchmark/kernels/<name>.py
    kernel_time_s: Dict[str, float]  # name -> device seconds of one dispatch (trace)
    kernel_dispatches: Dict[str, int]  # name -> dispatches counted in the window
    lanes: int  # lanes of one dispatch: the engines' bucket
    busy_s: Optional[float]  # device busy seconds in the window (None: not traced)

    def total(self, key: str) -> float:
        return sum(d[key] for d in self.engine_deltas)
