"""Median of t_result minus the modelled kernel end: the launch call's return,
the result's way back to the host and the worker's wait for the interpreter
lock.  Negative where the model of benchmark/spans.py fails: reported as it
is, never clipped."""

from benchmark import spans

DECLARATION = {"unit": "ms", "better": "lower", "source": "program_span",
               "layer": "engine queues", "moves": "finality_mean_ms"}


def read(obs):
    return spans.p50_ms(obs, "result_return_ns")
