"""Seconds of jaxpr tracing before the window opens (nested traces counted
once): the part of setup_s that no compile cache removes."""

from benchmark import spans

DECLARATION = {"unit": "s", "better": "lower", "source": "program_span",
               "layer": "host runtime", "moves": "setup_s"}


def read(obs):
    return spans.seconds(obs, "jax_trace_before_ns")
