"""Client robustness: retransmission, per-request timeout, reply dedup
(reference clients rely on stream replay, core/message-handling.go:316-350;
this build's client retransmits explicitly — VERDICT r1 weak #8)."""

import asyncio

import pytest

from minbft_tpu import api
from minbft_tpu.client import new_client
from conftest import all_reach, ledgers_reach, make_cluster as _cluster
from minbft_tpu.sample.conn.inprocess import InProcessClientConnector


class _LossyClientConnector(api.ReplicaConnector):
    """Drops the first ``drop`` messages of every stream — the fault the
    retransmitter exists for."""

    def __init__(self, inner: api.ReplicaConnector, drop: int):
        self._inner = inner
        self._drop = drop

    def replica_message_stream_handler(self, replica_id):
        inner_handler = self._inner.replica_message_stream_handler(replica_id)
        if inner_handler is None:
            return None
        drop = self._drop

        class _Lossy(api.MessageStreamHandler):
            async def handle_message_stream(self, in_stream):
                async def filtered():
                    seen = 0
                    async for data in in_stream:
                        seen += 1
                        if seen <= drop:
                            continue  # lost on the wire
                        yield data

                async for out in inner_handler.handle_message_stream(filtered()):
                    yield out

        return _Lossy()


def test_retransmit_recovers_lost_request():
    async def run():
        replicas, c_auths, stubs, ledgers = await _cluster()
        # every replica drops the client's first message: without
        # retransmission the request would hang forever
        conn = _LossyClientConnector(InProcessClientConnector(stubs), drop=1)
        client = new_client(
            0, 4, 1, c_auths[0], conn, seq_start=0, retransmit_interval=0.1
        )
        await client.start()
        result = await asyncio.wait_for(client.request(b"lossy-op"), 30)
        assert result
        await client.stop()
        for r in replicas:
            await r.stop()

    asyncio.run(run())


def test_request_timeout_without_retransmit():
    async def run():
        replicas, c_auths, stubs, ledgers = await _cluster()
        conn = _LossyClientConnector(InProcessClientConnector(stubs), drop=10**9)
        client = new_client(0, 4, 1, c_auths[0], conn, seq_start=0)
        await client.start()
        with pytest.raises(asyncio.TimeoutError):
            await client.request(b"never", timeout=0.3)
        await client.stop()
        for r in replicas:
            await r.stop()

    asyncio.run(run())


class _DuplicatingConnector(api.ReplicaConnector):
    """Delivers every outgoing message twice — guarantees the replicas'
    duplicate-REQUEST path executes (no timing luck involved)."""

    def __init__(self, inner: api.ReplicaConnector):
        self._inner = inner

    def replica_message_stream_handler(self, replica_id):
        inner_handler = self._inner.replica_message_stream_handler(replica_id)
        if inner_handler is None:
            return None

        class _Dup(api.MessageStreamHandler):
            async def handle_message_stream(self, in_stream):
                async def doubled():
                    async for data in in_stream:
                        yield data
                        yield data  # the duplicate

                async for out in inner_handler.handle_message_stream(doubled()):
                    yield out

        return _Dup()


def test_duplicate_request_replied_but_executed_once():
    """Replicas reply to a duplicate REQUEST (the client may be retrying a
    lost reply — reference message-handling.go:396-403) but execute it
    exactly once."""

    async def run():
        replicas, c_auths, stubs, ledgers = await _cluster()
        conn = _DuplicatingConnector(InProcessClientConnector(stubs))
        client = new_client(0, 4, 1, c_auths[0], conn, seq_start=0)
        await client.start()
        assert await asyncio.wait_for(client.request(b"once"), 30)
        assert await asyncio.wait_for(client.request(b"twice"), 30)
        # let the duplicates drain, then check exactly-once execution
        await asyncio.sleep(0.3)
        await ledgers_reach(ledgers, 2)
        assert all(lg.length == 2 for lg in ledgers), [lg.length for lg in ledgers]
        await client.stop()
        for r in replicas:
            await r.stop()

    asyncio.run(run())


def test_ed25519_scheme_cluster_commit():
    """Full commit with the Ed25519 signature scheme (BASELINE config 5's
    scheme) on the SIM backend."""

    async def run():
        replicas, c_auths, stubs, ledgers = await _cluster(scheme="ed25519")
        client = new_client(0, 4, 1, c_auths[0], InProcessClientConnector(stubs))
        await client.start()
        assert await asyncio.wait_for(client.request(b"ed-op"), 60)
        await client.stop()
        for r in replicas:
            await r.stop()

    asyncio.run(run())


class _FlakyClientConnector(api.ReplicaConnector):
    """Every replica's FIRST stream swallows one frame and dies — the
    mid-flight connection drop the client's reconnect loop exists for.
    Later attempts delegate to the real connector."""

    def __init__(self, inner: api.ReplicaConnector):
        self._inner = inner
        self.attempts: dict = {}

    def replica_message_stream_handler(self, replica_id):
        inner_handler = self._inner.replica_message_stream_handler(replica_id)
        if inner_handler is None:
            return None
        outer = self

        class _Flaky(api.MessageStreamHandler):
            async def handle_message_stream(self, in_stream):
                n = outer.attempts.get(replica_id, 0) + 1
                outer.attempts[replica_id] = n
                if n == 1:
                    # consume the request, then the connection drops: the
                    # frame is gone — no retransmit timer is configured, so
                    # only the reconnect re-send can ever recover it
                    async for _ in in_stream:
                        return
                    yield b""  # pragma: no cover - async-generator marker
                    return
                async for out in inner_handler.handle_message_stream(in_stream):
                    yield out

        return _Flaky()


def test_client_reconnects_after_stream_drop():
    """A dropped replica stream is redialed with backoff and every pending
    request re-sent: losing >f streams permanently would wedge all future
    requests (f+1 matching replies needed) even with healthy replicas."""

    async def run():
        replicas, c_auths, stubs, ledgers = await _cluster()
        conn = _FlakyClientConnector(InProcessClientConnector(stubs))
        client = new_client(0, 4, 1, c_auths[0], conn, seq_start=0)
        await client.start()
        # no retransmit_interval: completion proves the reconnect re-send
        result = await asyncio.wait_for(client.request(b"flaky-op"), 30)
        assert result
        # f+1 replies end the request; the last streams' redials follow
        await all_reach(lambda: [conn.attempts.get(i, 0) for i in range(4)], 2)
        await client.stop()
        for r in replicas:
            await r.stop()

    asyncio.run(run())


def test_reconnect_backoff_ladder():
    """Shared redial policy: exponential growth to the cap, reset only
    after a lived connection (a crash-looping peer must not be rewarded)."""
    from minbft_tpu.utils.backoff import ReconnectBackoff

    b = ReconnectBackoff(start_s=0.2, cap_s=10.0, lived_reset_s=5.0,
                         jitter_frac=0.0)
    assert [b.next_delay(0.0) for _ in range(7)] == [
        0.2, 0.4, 0.8, 1.6, 3.2, 6.4, 10.0,
    ]
    assert b.next_delay(0.0) == 10.0  # pinned at the cap
    assert b.next_delay(6.0) == 0.2   # lived >5s: ladder restarts
    assert b.next_delay(0.1) == 0.4


def test_reconnect_backoff_default_jitter_desynchronizes():
    """Two ladders born in the same tick (a partition heal ends every
    stream at once) must NOT redial in lockstep: the default jitter makes
    their delay sequences diverge while staying in the +-25% envelope."""
    import random

    from minbft_tpu.utils.backoff import ReconnectBackoff

    a = ReconnectBackoff(rng=random.Random(1))
    b = ReconnectBackoff(rng=random.Random(2))
    da = [a.next_delay(0.0) for _ in range(6)]
    db = [b.next_delay(0.0) for _ in range(6)]
    assert da != db
    ladder = 0.2
    for x, y in zip(da, db):
        for d in (x, y):
            assert ladder * 0.75 - 1e-9 <= d <= min(ladder * 1.25, 10.0) + 1e-9
        ladder = min(ladder * 2.0, 10.0)


def test_retransmit_backoff_ladder():
    """Client retransmit policy: capped exponential with jitter — the
    un-jittered ladder doubles from start to the 8x default cap, jittered
    delays stay in the envelope, and start_s must be positive."""
    import random

    import pytest

    from minbft_tpu.utils.backoff import RetransmitBackoff

    b = RetransmitBackoff(0.1, jitter_frac=0.0)
    assert [round(b.next_delay(), 10) for _ in range(6)] == [
        0.1, 0.2, 0.4, 0.8, 0.8, 0.8,
    ]
    b2 = RetransmitBackoff(0.1, cap_s=0.3, jitter_frac=0.0)
    assert [round(b2.next_delay(), 10) for _ in range(4)] == [
        0.1, 0.2, 0.3, 0.3,
    ]
    jb = RetransmitBackoff(0.1, jitter_frac=0.25, rng=random.Random(7))
    ladder = 0.1
    seen_off_ladder = False
    for _ in range(8):
        d = jb.next_delay()
        assert ladder * 0.75 - 1e-9 <= d <= min(ladder * 1.25, 0.8) + 1e-9
        seen_off_ladder = seen_off_ladder or abs(d - ladder) > 1e-9
        ladder = min(ladder * 2.0, 0.8)
    assert seen_off_ladder  # jitter actually moved the delays
    with pytest.raises(ValueError):
        RetransmitBackoff(0.0)


class _ChaosClientConnector(api.ReplicaConnector):
    """Kills every stream after it has delivered ``frames_per_life`` reply
    frames — repeated mid-run drops under pipelined load, the worst case
    for the redial loop's queue swap + pending re-send."""

    def __init__(self, inner: api.ReplicaConnector, frames_per_life: int):
        self._inner = inner
        self._frames_per_life = frames_per_life
        self.drops = 0

    def replica_message_stream_handler(self, replica_id):
        inner_handler = self._inner.replica_message_stream_handler(replica_id)
        if inner_handler is None:
            return None
        outer = self

        class _Chaos(api.MessageStreamHandler):
            async def handle_message_stream(self, in_stream):
                served = 0
                async for out in inner_handler.handle_message_stream(in_stream):
                    yield out
                    served += 1
                    if served >= outer._frames_per_life:
                        outer.drops += 1
                        return  # the connection dies mid-conversation

        return _Chaos()


def test_client_pipelined_load_survives_repeated_stream_drops():
    """30 pipelined requests complete while every replica stream dies
    after each 3 delivered frames — the redial loop must keep swapping
    queues and re-sending without losing or double-counting any request."""

    async def run():
        replicas, c_auths, stubs, ledgers = await _cluster()
        conn = _ChaosClientConnector(InProcessClientConnector(stubs), 3)
        client = new_client(0, 4, 1, c_auths[0], conn, seq_start=0, max_inflight=10)
        await client.start()
        results = await asyncio.wait_for(
            asyncio.gather(
                *(client.request(b"chaos-%d" % i) for i in range(30))
            ),
            60,
        )
        assert all(results)
        assert conn.drops > 0, "chaos connector never dropped a stream"
        # exactly-once execution despite every re-send
        await ledgers_reach(ledgers, 30)
        assert all(lg.length == 30 for lg in ledgers), [lg.length for lg in ledgers]
        await client.stop()
        for r in replicas:
            await r.stop()

    asyncio.run(run())


class _CountingConnector(api.ReplicaConnector):
    """Transparent passthrough that counts dials per replica."""

    def __init__(self, inner: api.ReplicaConnector):
        self._inner = inner
        self.dials: dict = {}

    def replica_message_stream_handler(self, replica_id):
        inner_handler = self._inner.replica_message_stream_handler(replica_id)
        if inner_handler is None:
            return None
        outer = self

        class _C(api.MessageStreamHandler):
            async def handle_message_stream(self, in_stream):
                outer.dials[replica_id] = outer.dials.get(replica_id, 0) + 1
                async for out in inner_handler.handle_message_stream(in_stream):
                    yield out

        return _C()


def test_client_reply_verifier_outage_poisons_stream_but_never_severs():
    """Non-auth exceptions in reply handling (e.g. a transient verifier
    backend outage) cost frames, then — after a consecutive run — the
    STREAM (backoff redial), but never the connection permanently: a
    transient outage severing >f streams forever would wedge every future
    request against healthy replicas."""

    async def run():
        from minbft_tpu.client.client import _MAX_CONSECUTIVE_REPLY_ERRORS

        replicas, c_auths, stubs, ledgers = await _cluster()
        auth = c_auths[0]
        real_verify = auth.verify_message_authen_tag
        state = {"fail": True, "raised": 0}
        # pigeonhole: this many raises across 4 streams forces at least
        # one stream past the per-stream guard, whatever its value
        outage = 4 * _MAX_CONSECUTIVE_REPLY_ERRORS + 4

        async def flaky_verify(role, rid, data, sig):
            if state["fail"] and role == api.AuthenticationRole.REPLICA:
                state["raised"] += 1
                if state["raised"] >= outage:
                    state["fail"] = False
                raise RuntimeError("verifier backend outage")
            return await real_verify(role, rid, data, sig)

        auth.verify_message_authen_tag = flaky_verify
        conn = _CountingConnector(InProcessClientConnector(stubs))
        client = new_client(
            0, 4, 1, auth, conn, seq_start=0, retransmit_interval=0.05
        )
        await client.start()
        result = await asyncio.wait_for(client.request(b"verifier-outage"), 30)
        assert result
        # at least one stream hit the consecutive-failure guard and was
        # redialed rather than severed
        assert max(conn.dials.values()) >= 2, conn.dials
        assert state["raised"] >= outage - 4, state
        await client.stop()
        for r in replicas:
            await r.stop()

    asyncio.run(run())


def test_stop_fails_inflight_requests_instead_of_hanging():
    """stop() must resolve in-flight requests with an error: their reply
    streams are gone, so leaving the futures pending parks the callers
    forever."""

    async def run():
        replicas, c_auths, stubs, ledgers = await _cluster()
        conn = _LossyClientConnector(InProcessClientConnector(stubs), drop=10**9)
        client = new_client(0, 4, 1, c_auths[0], conn, seq_start=0)
        await client.start()
        task = asyncio.ensure_future(client.request(b"never-answered"))
        await asyncio.sleep(0.1)
        await client.stop()
        with pytest.raises(ConnectionError):
            await asyncio.wait_for(task, 5)
        for r in replicas:
            await r.stop()

    asyncio.run(run())


def test_stop_fails_requests_parked_on_the_inflight_semaphore():
    """A caller that passed the started check but was parked on the
    max_inflight semaphore when stop() swept the pending map must fail
    fast too — registering after the sweep would hang forever."""

    async def run():
        replicas, c_auths, stubs, ledgers = await _cluster()
        conn = _LossyClientConnector(InProcessClientConnector(stubs), drop=10**9)
        client = new_client(0, 4, 1, c_auths[0], conn, seq_start=0, max_inflight=1)
        await client.start()
        t1 = asyncio.ensure_future(client.request(b"in-flight"))
        await asyncio.sleep(0.05)
        t2 = asyncio.ensure_future(client.request(b"parked-on-semaphore"))
        await asyncio.sleep(0.05)
        await client.stop()
        with pytest.raises(ConnectionError):
            await asyncio.wait_for(t1, 5)
        with pytest.raises(ConnectionError):
            await asyncio.wait_for(t2, 5)
        for r in replicas:
            await r.stop()

    asyncio.run(run())
