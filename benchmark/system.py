"""The system under test, built from a configuration file through the
program's own entry points, and the harness's three observers of it.

Built as ``chip_smoke.py``'s cluster phase builds it:
``placement.start_local_cluster`` (one ``BatchVerifier(max_batch=512,
buckets=(512,))`` per replica, no shared verdicts, every engine warmed off
the clock, a ``SimpleLedger`` per replica), clients from
``client.new_client`` over ``InProcessClientConnector``.

The observers use the program's public interfaces only (a later PR may
refactor the program, and may not edit this file):

- :class:`Recorder`, an ``api.Authenticator`` around each client's own:
  it lists every reply the client accepted, in order;
- :class:`Tap`, an ``api.ReplicaConnector`` around each client's own: it
  slips forged replies in front of the real ones for the writes the
  traffic marks;
- :class:`Forger`, a stream of its own to every replica, which sends
  requests under an identity that never signs a valid one.
"""

from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Dict, List, Optional

from .manifest import ROOT, BenchmarkError, device_queues, load_kernels, load_verifier


class Recorder:
    """A client's authenticator, with a list of the replies it accepted:
    (replica id, the bytes signed, the signature), in order.  ``verify``
    False is the control ``replies_unverified``: the client takes every
    reply on trust."""

    def __init__(self, inner, verify: bool = True):
        self._inner = inner
        self._verify = verify
        self.accepted: List[tuple] = []

    @property
    def count(self) -> int:
        return len(self.accepted)

    def __getattr__(self, name):
        return getattr(self._inner, name)

    async def verify_message_authen_tag(self, role, peer_id, msg, tag) -> None:
        if self._verify:
            await self._inner.verify_message_authen_tag(role, peer_id, msg, tag)
        self.accepted.append((peer_id, msg, tag))


class _TapHandler:
    def __init__(self, tap: "Tap", replica_id: int, inner):
        self._tap, self._rid, self._inner = tap, replica_id, inner

    async def handle_message_stream(self, in_stream):
        tap, rid = self._tap, self._rid
        waiting: List[bytes] = []

        async def outgoing():
            async for data in in_stream:
                if tap.shadowed and rid < tap.streams:
                    waiting.extend(tap.forged_replies(rid, data))
                yield data

        async for frame in self._inner.handle_message_stream(outgoing()):
            while waiting:
                yield waiting.pop(0)
            yield frame


class Tap:
    """A client's connector.  For a write marked by :meth:`shadow`, the
    first ``f+1`` replica streams each carry a forged REPLY (a wrong result
    under a random signature) ahead of that replica's real one: a client
    that verifies drops them, one that does not acknowledges the wrong
    result on them."""

    def __init__(self, inner, f: int):
        self._inner = inner
        self.streams = f + 1
        self.shadowed: Dict[bytes, tuple] = {}

    def shadow(self, op: bytes, wrong_result: bytes, signature: bytes) -> None:
        self.shadowed[op] = (wrong_result, signature)

    def unshadow(self, op: bytes) -> None:
        self.shadowed.pop(op, None)

    def forged_replies(self, replica_id: int, data: bytes) -> List[bytes]:
        from minbft_tpu.messages import CodecError, Reply, Request, marshal
        from minbft_tpu.messages import split_multi, unmarshal

        out = []
        try:
            frames = split_multi(data)
        except CodecError:
            return out
        for frame in frames:
            try:
                msg = unmarshal(frame)
            except CodecError:
                continue
            if isinstance(msg, Request) and msg.operation in self.shadowed:
                wrong, signature = self.shadowed[msg.operation]
                out.append(marshal(Reply(
                    replica_id=replica_id, client_id=msg.client_id,
                    seq=msg.seq, result=wrong, signature=signature,
                )))
        return out

    def replica_message_stream_handler(self, replica_id: int):
        inner = self._inner.replica_message_stream_handler(replica_id)
        return None if inner is None else _TapHandler(self, replica_id, inner)


class Forger:
    """Sends REQUESTs with random signatures to every replica, as client
    ``client_id``: an identity whose key the replicas know and that never
    sends a valid request, so nothing of a real client is disturbed."""

    def __init__(self, connector, n: int, client_id: int):
        self._connector = connector
        self._n = n
        self._client_id = client_id
        self._seq = 0
        self._queues: List[asyncio.Queue] = []
        self._tasks: List[asyncio.Task] = []
        self.sent = 0

    async def start(self) -> None:
        for rid in range(self._n):
            handler = self._connector.replica_message_stream_handler(rid)
            q: asyncio.Queue = asyncio.Queue()
            self._queues.append(q)
            self._tasks.append(asyncio.ensure_future(self._stream(handler, q)))

    @staticmethod
    async def _stream(handler, q: asyncio.Queue) -> None:
        async def outgoing():
            while True:
                yield await q.get()

        async for _ in handler.handle_message_stream(outgoing()):
            pass  # a reply to a forged request would show in the ledgers

    def send(self, op: bytes, signature: bytes) -> None:
        from minbft_tpu.messages import Request, marshal

        self._seq += 1
        data = marshal(Request(
            client_id=self._client_id, seq=self._seq, operation=op,
            signature=signature,
        ))
        for q in self._queues:
            q.put_nowait(data)
        self.sent += 1

    async def stop(self) -> None:
        for t in self._tasks:
            t.cancel()
        await asyncio.gather(*self._tasks, return_exceptions=True)


@dataclasses.dataclass
class System:
    config: dict
    kernels: Dict[str, object]  # the configuration's benchmark/kernels/ files, in its order
    verifier: object  # its scheme's benchmark/verifiers/ file (the comparison's reference)
    cluster: object  # placement.LocalCluster
    store: object  # the KeyStore (public keys for the comparison)
    n_clients: int
    clients: List[object]
    recorders: List[Recorder]
    taps: List[Tap]
    forger: Forger
    engines_warm_s: float
    # Everything this process has sent, over all its windows: the ledgers
    # hold it all, and the comparison asks what else they hold.
    requested: set = dataclasses.field(default_factory=set)
    forged_sent: set = dataclasses.field(default_factory=set)
    root: str = ROOT  # the checkout whose files the cell was loaded from
    # The faults that the windows' schedules applied (generator.Applied), in
    # order, over all the process's windows: a crashed replica stays down.
    faults_applied: list = dataclasses.field(default_factory=list)
    fault_kinds: dict = dataclasses.field(default_factory=dict)  # benchmark/faults/ files in use
    # For the control ``view_unexplained`` alone: replicas taken down behind
    # the schedule's back, which are down and explain no view.
    down_unexplained: list = dataclasses.field(default_factory=list)

    @property
    def engines(self):
        return self.cluster.engines

    @property
    def queues(self) -> List[str]:
        """The device queues: the kernels' distinct ``QUEUE``s, in their order."""
        return device_queues(self.kernels)

    async def stop(self) -> None:
        await self.forger.stop()
        for c in self.clients:
            await c.stop()
        await self.cluster.stop()


def _check_supported(config: dict) -> None:
    want = {"connector": "inprocess", "groups": 1, "state_machine": "SimpleLedger"}
    for key, value in want.items():
        if config.get(key) != value:
            raise BenchmarkError(
                f"configuration {config.get('name')}: {key}={config.get(key)!r} "
                f"has no builder in benchmark/system.py yet (only {value!r})"
            )
    if not config["engine"].get("per_replica") or len(config["engine"]["buckets"]) != 1:
        raise BenchmarkError("only one engine per replica with one bucket is built yet")


async def attach_clients(system: System, client_f: Optional[int] = None,
                         verify_replies: bool = True) -> None:
    """Start the system's clients (stopping any it has).  ``client_f`` and
    ``verify_replies`` exist for the controls alone: a client that takes f
    replies for a quorum, a client that takes replies on trust."""
    from minbft_tpu.client import new_client
    from minbft_tpu.sample.conn.inprocess import InProcessClientConnector

    for c in system.clients:
        await c.stop()
    system.clients.clear()
    system.recorders.clear()
    system.taps.clear()
    n, f = system.config["n"], system.config["f"]
    for c in range(system.n_clients):
        recorder = Recorder(system.store.client_authenticator(c), verify_replies)
        tap = Tap(InProcessClientConnector(system.cluster.stubs), f)
        client = new_client(
            c, n, f if client_f is None else client_f, recorder, tap,
            retransmit_interval=30.0,
        )
        await client.start()
        system.clients.append(client)
        system.recorders.append(recorder)
        system.taps.append(tap)


async def build(cell, config: dict, n_clients: int, on_cpu: bool = False) -> System:
    """Start the cluster of ``config`` (the cell's configuration as this
    run sizes it) and ``n_clients`` clients, and commit one write
    (first-contact USIG epochs) before any window."""
    from minbft_tpu.sample.authentication import generate_testnet_keys
    from minbft_tpu.sample.config import SimpleConfiger
    from minbft_tpu.sample.conn.inprocess import InProcessClientConnector
    from minbft_tpu.sample.peer.placement import start_local_cluster

    _check_supported(config)
    kernels, verifier = load_kernels(cell), load_verifier(cell)
    asyncio.get_running_loop().set_task_factory(asyncio.eager_task_factory)
    n, f = config["n"], config["f"]
    store = generate_testnet_keys(
        n, n_clients=n_clients + 1, scheme=config["scheme"], usig_spec=config["usig"]
    )
    timers = {k: config[k] for k in ("timeout_request", "timeout_prepare")}
    if "timeout_viewchange" in config:  # else the program's default
        timers["timeout_viewchange"] = config["timeout_viewchange"]
    cfg = SimpleConfiger(n=n, f=f, **timers)
    t0 = time.perf_counter()
    cluster = await start_local_cluster(
        store, cfg, batch=config["engine"]["max_batch"], on_cpu=on_cpu
    )
    warm_s = time.perf_counter() - t0
    if not all(e is not None for e in cluster.engines):
        await cluster.stop()
        raise BenchmarkError(f"a replica chose host crypto: {cluster.placement}")
    forger = Forger(InProcessClientConnector(cluster.stubs), n, n_clients)
    system = System(config, kernels, verifier, cluster, store, n_clients, [], [], [],
                    forger, warm_s, root=cell.root)
    try:
        await attach_clients(system)
        await forger.start()
        op = b"warm-up, before any window"
        system.requested.add(op)
        await asyncio.wait_for(system.clients[0].request(op), 300)
    except BaseException:
        await system.stop()
        raise
    return system
