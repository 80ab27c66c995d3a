"""Share of the window in which the device was idle, nothing was queued or in
dispatch, and the event loop was running: protocol, codec and callers set
the pace."""

from benchmark import spans

DECLARATION = {"unit": "share", "better": "lower", "source": "program_span",
               "layer": "device", "moves": "goodput_rps"}


def read(obs):
    return spans.idle_share(obs, "loop_busy")
