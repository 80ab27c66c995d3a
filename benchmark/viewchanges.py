"""The program's view-change rows, reduced to the first view change of one
window.

``minbft_tpu.obs.trace.timeline()["viewchange"]`` gives one row per step
of a replica's change to a new view (``demand``: it sent its own
REQ-VIEW-CHANGE; ``started``: f+1 demands, it sends its VIEW-CHANGE;
``new_view_sent``: as the new primary it sends NEW-VIEW; ``entered``: it
stands in the view) and ``verify_items``, a row a validation: the
certificate checks a replica's validators handed on to its engine.  A
replica that is down never enters the view, so the replicas read are those
that entered it: a crashed replica's rows (a demand its own timer sent
after it went, say) are not.  Against a program older than the section,
with a ring that lost rows, or in a window in which no view change began
and ended, every reader here returns None.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from . import spans


@dataclasses.dataclass(frozen=True)
class Span:
    """One view change, as the readers see it."""

    view: int  # the new view
    replicas: tuple  # those that entered it
    first_demand: int  # ns: the first demand among them
    last_entered: int  # ns: the last of them in the view
    verify_items: int  # their certificate checks for the change

    @property
    def ms(self) -> float:
        return (self.last_entered - self.first_demand) / 1e6


def first_in(section: dict, opened: int, closed: int) -> Optional[Span]:
    """The first view change whose first demand (among the replicas that
    entered the view) falls in [opened, closed), read from the rows
    written since ``opened`` (a process that ran a cluster before this
    window keeps that cluster's rows); None where there is none, or where a
    ring dropped rows that could be the window's."""
    for ring in ("rows", "verify_items"):
        dropped = section["dropped" if ring == "rows" else "verify_dropped"]
        if dropped and (not section[ring] or section[ring][0][3] > opened):
            return None
    rows = [row for row in section["rows"] if row[3] >= opened]
    entered: dict = {}
    for replica, view, stage, t in rows:
        if stage == "entered":
            entered.setdefault(view, {}).setdefault(replica, t)
    for view in sorted(entered):
        who = entered[view]
        demands = [t for r, v, stage, t in rows if v == view and stage == "demand" and r in who]
        if not demands or min(demands) >= closed:
            continue
        items = sum(n for r, v, n, t in section["verify_items"]
                    if v == view and r in who and t >= opened)
        return Span(view, tuple(sorted(who)), min(demands), max(who.values()), items)
    return None


def first_view_change(obs) -> Optional[Span]:
    """:func:`first_in` of the process's timeline over ``obs``'s window,
    made once per ``Observations`` and kept on it."""
    if "_viewchange" not in obs.__dict__:
        found = None
        tl = spans.timeline()
        if tl is not None and "viewchange" in tl:
            window = spans.window(obs, tl)
            if window is not None:
                found = first_in(tl["viewchange"], *window)
        obs.__dict__["_viewchange"] = found
    return obs.__dict__["_viewchange"]
