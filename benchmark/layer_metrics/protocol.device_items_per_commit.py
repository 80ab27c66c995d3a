"""Signatures checked and made on the device per acknowledged write."""

DECLARATION = {"unit": "items/commit", "better": "lower", "source": "program_counter",
               "layer": "protocol", "moves": "goodput_rps"}


def read(obs):
    if not obs.commits:
        return None
    return (obs.total("verify_items") + obs.total("sign_items")) / obs.commits
