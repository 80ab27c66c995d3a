"""Share of the window in which a generation-2 pass of the collector held
the one event loop (gc.callbacks, timed in the harness)."""

DECLARATION = {"unit": "share", "better": "lower", "source": "host_clock",
               "layer": "host runtime", "moves": "finality_p95_ms"}


def read(obs):
    if 2 not in obs.gc_pause_s:
        return None
    return obs.gc_pause_s[2] / obs.window_s
