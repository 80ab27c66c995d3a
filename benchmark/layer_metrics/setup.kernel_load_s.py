"""Seconds this process spent loading kernels' compiled executables from the
kernel store: ``load_s`` summed over the kernels of
``timeline()["jax"]["kernel_store"]`` (the digest, the read and the
deserialization of every load, failed ones included).  Warm-up loads each
kernel on the main thread; a load that slips onto a dispatcher's worker
thread takes about five times as long, and shows here first.  A process
that met no store reads 0; a program without the counter reads None."""

from benchmark import spans

DECLARATION = {"unit": "s", "better": "lower", "source": "program_span",
               "layer": "host runtime", "moves": "setup_s"}


def read(obs):
    tl = spans.timeline()
    store = None if tl is None else tl["jax"].get("kernel_store")
    if store is None:
        return None
    return sum(row["load_s"] for row in store.values())
