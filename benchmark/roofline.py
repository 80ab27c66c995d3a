"""A kernel's share of its roofline: the least time the chip could take for
the dispatch's textbook work (the larger of operations over the peak rate
and bytes over the memory bandwidth) over the device time the trace gives.
"""

from __future__ import annotations

from typing import Optional

from .manifest import load_peaks


def least_time_s(work: dict, peaks: dict) -> tuple:
    """-> (seconds, which bound binds: 'compute' or 'memory')."""
    compute = work["ops"] / peaks[work["peak"]]
    memory = work["bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")


def share_percent(obs, kernel: str) -> Optional[float]:
    """None where the trace holds no time for the kernel, and on the CPU
    backend of a rehearsal, which has no peaks to share."""
    seconds = obs.kernel_time_s.get(kernel)
    if not seconds or obs.platform == "cpu":
        return None
    least, _ = least_time_s(obs.kernels[kernel].work(obs.lanes), load_peaks(obs.device_kind))
    return 100.0 * least / seconds
