"""The rest of the device's idle time: nothing queued, nothing in dispatch, no
collector pass, and the loop blocked in its selector with nothing to run."""

from benchmark import spans

DECLARATION = {"unit": "share", "better": "lower", "source": "program_span",
               "layer": "device", "moves": "goodput_rps"}


def read(obs):
    return spans.idle_share(obs, "loop_idle")
