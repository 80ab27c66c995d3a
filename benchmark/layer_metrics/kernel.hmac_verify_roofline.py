"""The HMAC-SHA256 verify kernel's (USIG certificate checks) share of its
roofline, as for Ed25519 verify; the larger bound is the dispatch's bytes."""

from benchmark.roofline import share_percent

DECLARATION = {"unit": "%", "better": "higher", "source": "device_trace",
               "layer": "kernels", "moves": "goodput_rps"}


def read(obs):
    return share_percent(obs, "hmac_verify")
