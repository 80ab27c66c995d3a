"""Median of the same finality times that finality_mean_ms averages."""

from benchmark.observe import percentile

DECLARATION = {"unit": "ms", "better": "lower", "source": "host_clock",
               "layer": "client", "moves": "finality_mean_ms"}


def read(obs):
    return percentile(obs.latencies_ms, 50) if obs.latencies_ms else None
