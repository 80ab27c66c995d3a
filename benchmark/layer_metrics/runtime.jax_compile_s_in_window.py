"""Seconds of JAX tracing, lowering, compiling or cache retrieval inside the
window (jax.monitoring events on the process timeline): expected 0, since
every shape is warmed in set-up."""

from benchmark import spans

DECLARATION = {"unit": "s", "better": "lower", "source": "program_span",
               "layer": "host runtime", "moves": "finality_p95_ms"}


def read(obs):
    return spans.seconds(obs, "jax_in_window_ns")
