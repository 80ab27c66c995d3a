"""Share of the window in which no modelled kernel ran on the device while a
pass of the collector held the process (benchmark/spans.py, the first class
that holds; the five device.idle_*_share sum to device.idle_share)."""

from benchmark import spans

DECLARATION = {"unit": "share", "better": "lower", "source": "program_span",
               "layer": "device", "moves": "finality_p95_ms"}


def read(obs):
    return spans.idle_share(obs, "gc")
