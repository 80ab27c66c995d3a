"""Share of the window in which the device was idle while some shipped batch
was between t_flush and t_prep_end: thread hop or host prep (from t_prep_end
on, the launch, its kernel is the device's to start)."""

from benchmark import spans

DECLARATION = {"unit": "share", "better": "lower", "source": "program_span",
               "layer": "device", "moves": "goodput_rps"}


def read(obs):
    return spans.idle_share(obs, "dispatch_host")
