"""The Ed25519 verify kernel's share of its roofline: textbook work of one
dispatch (benchmark/kernels/ed25519_verify.py) over the kernel's device time
in the trace.  All dispatched lanes count; padding is the engine's waste."""

from benchmark.roofline import share_percent

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "goodput_rps"}


def read(obs):
    return share_percent(obs, "ed25519_verify")
