"""The one traffic generator: a mix is a data file, this code reads any.

``benchmark/traffic/<mix>.json`` gives ``loop`` (closed|open), ``clients``,
``depth`` (requests in flight per client, closed loop), ``rate_rps`` (open
loop), ``read_share``, ``payload_bytes``, ``forged_request_every``,
``forged_reply_every``, ``ack_wait_s`` and, where the deployment has a
failure in it, ``faults``: a schedule of ``{"at_s": seconds after the window
opens, "kind": a file of benchmark/faults/, "target": "primary" | "backup" |
a replica's id}`` (absent or empty: nothing fails).  Payloads, the forged
items' bytes and (open loop) the arrival times come from ``--seed``; every
seed gives the same number of clients, the same sizes, the same rate and the
same schedule.
"""

from __future__ import annotations

import asyncio
import dataclasses
import random
import re
import time
from typing import Dict, List, NamedTuple, Optional, Tuple, Union

from . import manifest
from .compare import CRASHES, running
from .manifest import BenchmarkError

_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")  # a file's name, as the contract has it


@dataclasses.dataclass(frozen=True)
class Fault:
    """One entry of a mix's schedule."""

    at_s: float
    kind: str
    target: Union[str, int]

    @classmethod
    def from_entry(cls, entry) -> "Fault":
        if not isinstance(entry, dict) or set(entry) != {"at_s", "kind", "target"}:
            raise BenchmarkError(f"a fault is {{at_s, kind, target}}, not {entry!r}")
        at_s, kind, target = entry["at_s"], entry["kind"], entry["target"]
        if isinstance(at_s, bool) or not isinstance(at_s, (int, float)) or at_s < 0:
            raise BenchmarkError(f"fault at_s {at_s!r}: seconds after the window opens")
        if not isinstance(kind, str) or not _NAME.match(kind):
            raise BenchmarkError(f"fault kind {kind!r}: the name of a file of benchmark/faults/")
        if target not in ("primary", "backup") and (
                isinstance(target, bool) or not isinstance(target, int) or target < 0):
            raise BenchmarkError(f"fault target {target!r}: primary, backup or a replica's id")
        return cls(float(at_s), kind, target)


def target_replica(system, target: Union[str, int]) -> int:
    """The replica a fault aimed at ``target`` hits, found when it fires.
    ``primary``: of the furthest view a running replica stands in
    (``metrics.current_view`` mod n), or the next running replica in the line
    of succession; ``backup``: the running replica last in that line."""
    if isinstance(target, int):
        return target
    up = running(system)
    n = system.config["n"]
    view = max(int(system.cluster.replicas[r].metrics.current_view) for r in up)
    line = [(view + k) % n for k in range(n)]
    if target == "backup":
        line.reverse()
    return next(r for r in line if r in up)


class Applied(NamedTuple):
    """A fault as it was applied: to which replica, and when (perf_counter)."""

    kind: str
    replica: int
    at: float


@dataclasses.dataclass(frozen=True)
class Mix:
    loop: str
    clients: int
    depth: int
    rate_rps: Optional[float]
    read_share: float
    payload_bytes: int
    forged_request_every: int
    forged_reply_every: int
    ack_wait_s: float
    faults: Tuple[Fault, ...] = ()

    @classmethod
    def from_file(cls, data: dict, override: Optional[dict] = None) -> "Mix":
        d = {f.name: data.get(f.name) for f in dataclasses.fields(cls)}
        d.update({k: v for k, v in (override or {}).items() if k in d})
        schedule = d["faults"] or ()
        if not isinstance(schedule, (list, tuple)):
            raise BenchmarkError(f"traffic faults {schedule!r}: a list of {{at_s, kind, target}}")
        d["faults"] = tuple(sorted(map(Fault.from_entry, schedule), key=lambda f: f.at_s))
        mix = cls(**d)
        if mix.loop not in ("closed", "open"):
            raise BenchmarkError(f"traffic loop {mix.loop!r}: closed or open")
        if mix.loop == "open" and not mix.rate_rps:
            raise BenchmarkError("an open loop needs rate_rps")
        if mix.read_share:
            raise BenchmarkError(
                "read_share > 0: the comparison does not hold reads to a "
                "reference yet (PERF.md, Open questions)"
            )
        if mix.payload_bytes < 16:
            raise BenchmarkError("payload_bytes under 16 cannot hold the unique head")
        return mix

    def against(self, config: dict, root: str = manifest.ROOT) -> Dict[str, object]:
        """The schedule held against the configuration it will run on, before
        any cluster starts -> {kind: module of benchmark/faults/<kind>.py}.
        A kind without a file, a kind the comparison has no rule for, a
        replica the cluster has not and more crashes than the f that the
        guarantees cover are errors."""
        kinds = {f.kind: manifest.by_name(root, "faults", f.kind, "fault kind")
                 for f in self.faults}
        for fault in self.faults:
            if fault.kind not in CRASHES:
                raise BenchmarkError(
                    f"fault kind {fault.kind!r}: benchmark/compare.py has no rule yet for "
                    f"judging a window with it (it has one for {sorted(CRASHES)})")
            if isinstance(fault.target, int) and fault.target >= config["n"]:
                raise BenchmarkError(
                    f"fault target {fault.target}: the cluster has replicas 0 to {config['n'] - 1}")
        crashes = sum(f.kind in CRASHES for f in self.faults)
        if crashes > config["f"]:
            raise BenchmarkError(
                f"the schedule crashes {crashes} replicas and the configuration's guarantees "
                f"hold up to f = {config['f']}: they promise nothing beyond f")
        return kinds


@dataclasses.dataclass
class Issued:
    """One write, from ``request()`` called to quorum reached."""

    client: int
    op: bytes
    due: float  # when it was due to be sent (closed loop: when it was sent)
    sent: float
    acked: Optional[float] = None  # None: never answered
    result: Optional[bytes] = None
    mark: int = 0  # the client's count of accepted replies at the ack
    shadowed: bool = False  # forged replies were aimed at this one


class Payloads:
    """Writes of ``payload_bytes`` bytes, each unique in the run: a head of
    window tag, client and count, then bytes drawn from the seed."""

    def __init__(self, seed: int, mix: Mix, tag: bytes = b"w"):
        self._rng = random.Random(seed)
        self._mix = mix
        self._tag = tag
        self._count: Dict[int, int] = {}

    def next(self, client: int) -> bytes:
        k = self._count.get(client, 0)
        self._count[client] = k + 1
        head = b"%s%02x.%06x." % (self._tag, client, k)
        return head + self._rng.randbytes(self._mix.payload_bytes - len(head))

    def forged(self) -> bytes:
        return b"forged." + self._rng.randbytes(self._mix.payload_bytes - 7)

    def garbage(self, n: int) -> bytes:
        return self._rng.randbytes(n)


class Window:
    """Drives one measured window over started clients and collects every
    write issued in it.  ``system`` is a :class:`benchmark.system.System`."""

    def __init__(self, system, mix: Mix, seed: int, seconds: float, tag: bytes = b"w"):
        self.system = system
        self.mix = mix
        self.seconds = seconds
        self.payloads = Payloads(seed, mix, tag)
        self.issued: List[Issued] = []
        self.forged_ops: List[bytes] = []
        self.opened = 0.0
        self.closed = 0.0
        self.generator_late_s: List[float] = []
        self.faults_applied: List[Applied] = []
        # the kinds' files, kept with the system (a control wraps one there)
        self._kinds = system.fault_kinds
        for kind, module in mix.against(system.config, system.root).items():
            self._kinds.setdefault(kind, module)
        self._count = 0

    async def _one(self, client_id: int, due: float) -> None:
        system, mix = self.system, self.mix
        op = self.payloads.next(client_id)
        self._count += 1
        shadow = mix.forged_reply_every and self._count % mix.forged_reply_every == 0
        forge = mix.forged_request_every and self._count % mix.forged_request_every == 0
        tap = system.taps[client_id]
        if shadow:
            tap.shadow(op, self.payloads.garbage(32), self.payloads.garbage(64))
        rec = Issued(client_id, op, due, time.perf_counter(), shadowed=bool(shadow))
        self.issued.append(rec)
        if forge:
            forged = self.payloads.forged()
            self.forged_ops.append(forged)
            system.forger.send(forged, self.payloads.garbage(64))
        try:
            rec.result = await asyncio.wait_for(
                system.clients[client_id].request(op),
                max(self.closed - time.perf_counter(), 0.0) + mix.ack_wait_s,
            )
            rec.acked = time.perf_counter()
            rec.mark = system.recorders[client_id].count
        except asyncio.TimeoutError:
            pass  # late beyond ack_wait_s: never answered
        finally:
            if shadow:
                tap.unshadow(op)

    async def _closed_slot(self, client_id: int) -> None:
        while time.perf_counter() < self.closed:
            await self._one(client_id, time.perf_counter())

    async def _open_loop(self) -> None:
        rng = random.Random(self.payloads.garbage(8))
        tasks, due, client = [], self.opened, 0
        while True:
            due += rng.expovariate(self.mix.rate_rps)
            if due >= self.closed:
                break
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            self.generator_late_s.append(max(time.perf_counter() - due, 0.0))
            tasks.append(asyncio.ensure_future(self._one(client, due)))
            client = (client + 1) % self.mix.clients
        await asyncio.gather(*tasks)

    async def _schedule(self) -> None:
        """The mix's faults, each ``at_s`` after the window opened; what the
        close finds not yet due is not applied."""
        for fault in self.mix.faults:
            due = self.opened + fault.at_s
            if due >= self.closed:
                break
            await asyncio.sleep(max(due - time.perf_counter(), 0.0))
            replica = target_replica(self.system, fault.target)
            applied = Applied(fault.kind, replica, time.perf_counter())
            # on the record before the replica goes: whoever looks meanwhile sees it down
            self.faults_applied.append(applied)
            self.system.faults_applied.append(applied)
            await self._kinds[fault.kind].apply(self.system, replica)

    async def run(self) -> None:
        self.opened = time.perf_counter()
        self.closed = self.opened + self.seconds
        schedule = asyncio.ensure_future(self._schedule())
        try:
            if self.mix.loop == "closed":
                await asyncio.gather(*[
                    self._closed_slot(c)
                    for c in range(self.mix.clients)
                    for _ in range(self.mix.depth)
                ])
            else:
                await self._open_loop()
        except BaseException:
            schedule.cancel()
            raise
        await schedule  # all of it was due before the close, which the load outlasts
