"""Share of the dispatches resolved inside the window that left their queue
because it held a whole bucket (flush reason ``full``; the others are
``idle``, ``completion``, ``timer``, ``direct``).  The dispatch ring's
``reason`` column, as ``timeline()`` names it, over every engine and queue."""

from benchmark import spans

DECLARATION = {"unit": "share", "better": "higher", "source": "program_span",
               "layer": "engine queues", "moves": "goodput_rps"}


def read(obs):
    a = spans.analysis(obs)
    if a is None:
        return None
    rows = spans.dispatch_rows(spans.timeline(), a.opened)
    if rows is None:
        return None  # a ring dropped rows written inside the window
    inside = [r for r in rows if a.opened <= r["t_resolved"] < a.closed]
    if not inside:
        return None
    return sum(r["reason"] == "full" for r in inside) / len(inside)
