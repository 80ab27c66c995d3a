"""Host time spent packing and finishing device batches (ops/p256.py's
prepare_packed, sign_prepare, sign_finish) per acknowledged write."""

DECLARATION = {"unit": "ms/commit", "better": "lower", "source": "program_counter",
               "layer": "host prep", "moves": "goodput_rps"}


def read(obs):
    if not obs.commits:
        return None
    return 1e3 * (obs.total("verify_prep_s") + obs.total("sign_prep_s")) / obs.commits
