"""Share of the verify kernels' lanes that carried padding."""

DECLARATION = {"unit": "share", "better": "lower", "source": "program_counter",
               "layer": "engine queues", "moves": "goodput_rps"}


def read(obs):
    padded, items = obs.total("verify_padded"), obs.total("verify_items")
    return padded / (padded + items) if padded + items else None
