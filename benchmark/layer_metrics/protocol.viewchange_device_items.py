"""The certificate checks of the first view change inside the window that
reached the replicas' engines: those its VIEW-CHANGE and NEW-VIEW validators
handed on past the replica's memo of checked certificates, summed over the
replicas that entered the new view (benchmark/viewchanges.py).  A message
validated once and embedded again counts once, and so does a certificate
that the replica had checked before."""

from benchmark import viewchanges

DECLARATION = {"unit": "items", "better": "lower", "source": "program_counter",
               "layer": "protocol", "moves": "outage_ms"}


def read(obs):
    found = viewchanges.first_view_change(obs)
    return None if found is None else found.verify_items
