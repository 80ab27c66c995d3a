"""Median of t_resolved - t_finish_end: from a dispatch's result being ready
on its worker thread to the event loop running its _run again."""

from benchmark import spans

DECLARATION = {"unit": "ms", "better": "lower", "source": "program_span",
               "layer": "engine queues", "moves": "finality_mean_ms"}


def read(obs):
    return spans.p50_ms(obs, "loop_wake_ns")
