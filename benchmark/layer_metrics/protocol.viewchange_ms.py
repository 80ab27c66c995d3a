"""The protocol's part of an outage: from the first demand of a replica
that entered the new view to the last such replica's entry, for the first
view change that began inside the window (benchmark/viewchanges.py).  The
request timer that ran out before the first demand is not in it."""

from benchmark import viewchanges

DECLARATION = {"unit": "ms", "better": "lower", "source": "program_span",
               "layer": "protocol", "moves": "outage_ms"}


def read(obs):
    found = viewchanges.first_view_change(obs)
    return None if found is None else found.ms
