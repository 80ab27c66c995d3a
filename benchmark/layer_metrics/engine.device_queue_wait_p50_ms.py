"""Median over the window's device dispatches of modelled kernel start minus
t_prep_end (the launch's start): a kernel waiting behind other engines'
kernels (benchmark/spans.py::device_intervals)."""

from benchmark import spans

DECLARATION = {"unit": "ms", "better": "lower", "source": "program_span",
               "layer": "engine queues", "moves": "finality_mean_ms"}


def read(obs):
    return spans.p50_ms(obs, "device_queue_wait_ns")
