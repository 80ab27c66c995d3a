"""The Ed25519 sign kernel's (r*B) share of its roofline, as for verify."""

from benchmark.roofline import share_percent

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "goodput_rps"}


def read(obs):
    return share_percent(obs, "ed25519_sign")
