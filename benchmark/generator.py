"""The one traffic generator: a mix is a data file, this code reads any.

``benchmark/traffic/<mix>.json`` gives ``loop`` (closed|open), ``clients``,
``depth`` (requests in flight per client, closed loop), ``rate_rps`` (open
loop), ``read_share``, ``payload_bytes``, ``forged_request_every``,
``forged_reply_every`` and ``ack_wait_s``.  Payloads, the forged items'
bytes and (open loop) the arrival times come from ``--seed``; every seed
gives the same number of clients, the same sizes and the same rate.
"""

from __future__ import annotations

import asyncio
import dataclasses
import random
import time
from typing import Dict, List, Optional

from .manifest import BenchmarkError


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

    @classmethod
    def from_file(cls, data: dict, override: Optional[dict] = None) -> "Mix":
        d = {f.name: data.get(f.name) for f in dataclasses.fields(cls)}
        d.update({k: v for k, v in (override or {}).items() if k in d})
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

    async def run(self) -> None:
        self.opened = time.perf_counter()
        self.closed = self.opened + self.seconds
        if self.mix.loop == "closed":
            await asyncio.gather(*[
                self._closed_slot(c)
                for c in range(self.mix.clients)
                for _ in range(self.mix.depth)
            ])
        else:
            await self._open_loop()
