"""Share of the window in which no operation ran on the device:
1 - busy_s / window_s, with busy_s as benchmark/tracing.py takes it."""

DECLARATION = {"unit": "share", "better": "lower", "source": "device_trace",
               "layer": "device", "moves": "goodput_rps"}


def read(obs):
    if obs.busy_s is None or obs.window_s <= 0:
        return None
    return 1.0 - obs.busy_s / obs.window_s
