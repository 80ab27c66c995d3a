"""Share of the window the event loop was blocked in its selector with
nothing runnable (obs/looplag.py's idle clock, 10 ms slots)."""

from benchmark import spans

DECLARATION = {"unit": "share", "better": "higher", "source": "program_span",
               "layer": "host runtime", "moves": "goodput_rps"}


def read(obs):
    return spans.loop_idle_share(obs)
