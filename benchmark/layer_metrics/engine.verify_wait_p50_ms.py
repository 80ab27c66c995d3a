"""Median wait of an item in a verify queue, enqueue to dispatch, over the
cell's engines (VerifyStats.queue_wait, the window's delta)."""

from benchmark.observe import log2_bucket_percentile

DECLARATION = {"unit": "ms", "better": "lower", "source": "program_span",
               "layer": "engine queues", "moves": "finality_mean_ms"}


def read(obs):
    buckets = [sum(col) for col in zip(*[d["verify_wait_buckets"] for d in obs.engine_deltas])]
    p50 = log2_bucket_percentile(buckets, 50)
    return None if p50 is None else p50 * 1e3
