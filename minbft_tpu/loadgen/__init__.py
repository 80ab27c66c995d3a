"""Open-loop load harness (ISSUE 15): seeded arrival schedules, a
bounded-connection traffic generator measuring latency from SCHEDULED
arrival time, and the replay-census faithfulness contract.

README §Load testing has the methodology; ``peer load`` is the entry
point.
"""

from .arrivals import (
    Arrival,
    LoadSpec,
    Schedule,
    build_schedule,
    replay_census,
)
from .harness import OpenLoopGenerator

__all__ = [
    "Arrival",
    "LoadSpec",
    "Schedule",
    "build_schedule",
    "replay_census",
    "OpenLoopGenerator",
]
