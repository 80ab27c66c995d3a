"""USIG certificates checked on the device per acknowledged write: the
``hmac_sha256`` verify queue's items over all engines.  Every replica
checks every other replica's certificate, so this is the O(n^2) term of a
cluster; ``protocol.device_items_per_commit`` holds it together with the
request checks and reply signatures."""

DECLARATION = {"unit": "items/commit", "better": "lower", "source": "program_counter",
               "layer": "protocol", "moves": "goodput_rps"}


def read(obs):
    side = ("hmac_sha256", "verify")
    if not obs.commits or any(side not in d["items"] for d in obs.engine_deltas):
        return None  # no write acknowledged, or a deployment without that queue
    return sum(d["items"][side] for d in obs.engine_deltas) / obs.commits
