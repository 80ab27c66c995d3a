"""Share of the window in which the device was idle while items sat in an
engine queue, enqueued and not yet shipped (t_first_enqueue to t_flush)."""

from benchmark import spans

DECLARATION = {"unit": "share", "better": "lower", "source": "program_span",
               "layer": "device", "moves": "goodput_rps"}


def read(obs):
    return spans.idle_share(obs, "unflushed")
