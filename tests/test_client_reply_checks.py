"""The client's reply checks (client/client.py over utils/replycheck.py):
what a write costs in checks, which replies may form its quorum, what a
frame's replies have in common (one native call), and what a failing
checker or a stopping client leaves behind."""

import asyncio
import gc
import json
import os

import pytest

from minbft_tpu import api
from minbft_tpu.client import new_client
from minbft_tpu.messages import Reply, marshal, split_multi, unmarshal
from minbft_tpu.messages.codec import pack_multi
from minbft_tpu.sample.conn.inprocess import InProcessClientConnector
from minbft_tpu.utils import hostcrypto as hc
from minbft_tpu.utils import replycheck
from conftest import make_cluster as _cluster


class _ForgingConnector(api.ReplicaConnector):
    """The benchmark's ``Tap``, sharpened: on the first ``streams`` replica
    streams every real REPLY arrives behind a forged one (a wrong result
    under a random signature) for the same write, in the same frame."""

    def __init__(self, inner: api.ReplicaConnector, streams: int):
        self._inner = inner
        self._streams = streams

    def replica_message_stream_handler(self, replica_id):
        inner = self._inner.replica_message_stream_handler(replica_id)
        forge = replica_id < self._streams

        class _Handler(api.MessageStreamHandler):
            async def handle_message_stream(self, in_stream):
                async for data in inner.handle_message_stream(in_stream):
                    frames = []
                    for frame in split_multi(data):
                        msg = unmarshal(frame)
                        if forge and isinstance(msg, Reply):
                            frames.append(marshal(Reply(
                                replica_id=replica_id, client_id=msg.client_id,
                                seq=msg.seq, result=b"forged result",
                                signature=os.urandom(len(msg.signature)),
                            )))
                        frames.append(frame)
                    yield pack_multi(frames)

        return _Handler()


class _Accepted:
    """The benchmark's ``Recorder``: the replies the client's own
    authenticator let through, in order.  ``verify`` False takes them on
    trust (the control ``replies_unverified``)."""

    def __init__(self, inner, verify: bool = True):
        self._inner = inner
        self._verify = verify
        self.accepted = []

    def __getattr__(self, name):
        return getattr(self._inner, name)

    async def verify_message_authen_tag(self, role, peer_id, msg, tag):
        if self._verify:
            await self._inner.verify_message_authen_tag(role, peer_id, msg, tag)
        self.accepted.append((peer_id, msg, tag))


@pytest.fixture(autouse=True)
def _no_checker_left_over():
    """A loop that an earlier test dropped with its clients running lets
    go of the helper threads when it is collected."""
    gc.collect()


def _checker():
    """The running loop's checker while a client holds it."""
    return replycheck._CHECKERS.get(asyncio.get_running_loop())


async def _stop(client, replicas):
    await client.stop()
    for r in replicas:
        await r.stop()


@pytest.mark.parametrize("scheme", ["ecdsa-p256", "ed25519"])
@pytest.mark.parametrize("eager", [False, True], ids=["lazy_tasks", "eager_tasks"])
def test_quorum_forms_only_from_verified_replies_when_forged_ones_come_first(
    scheme, eager
):
    """Every real reply stands behind a forged one in its frame, and the
    forged ones agree with each other: none of them may count, each costs
    a check, and the write is acknowledged on the real result."""

    async def run():
        if eager:  # the benchmark's loops run tasks eagerly
            asyncio.get_running_loop().set_task_factory(asyncio.eager_task_factory)
        n, f = 7, 3
        replicas, c_auths, stubs, ledgers = await _cluster(n=n, f=f, scheme=scheme)
        auth = _Accepted(c_auths[0])
        conn = _ForgingConnector(InProcessClientConnector(stubs), n)
        client = new_client(0, n, f, auth, conn, seq_start=0)
        await client.start()
        writes = 4
        for i in range(writes):
            result = await asyncio.wait_for(client.request(b"op %d" % i), 30)
            assert result and result != b"forged result"
            # f+1 replies went through the authenticator before the ack,
            # each of a replica of its own, none of them forged
            assert len(auth.accepted) == (f + 1) * (i + 1)
            assert len({rid for rid, _, _ in auth.accepted[-(f + 1):]}) == f + 1
        stats = client.reply_checks
        assert stats.acked == writes
        assert stats.checked == 2 * (f + 1) * writes
        # each frame's pair was one native call
        assert (stats.batches, stats.off_lock) == ((f + 1) * writes, stats.checked)
        assert stats.to_dict()["inline"] == 0
        await _stop(client, replicas)

    asyncio.run(run())


def test_forged_replies_on_f_plus_one_streams_as_the_benchmark_plants_them():
    async def run():
        n, f = 7, 3
        replicas, c_auths, stubs, ledgers = await _cluster(n=n, f=f)
        auth = _Accepted(c_auths[0])
        conn = _ForgingConnector(InProcessClientConnector(stubs), f + 1)
        client = new_client(0, n, f, auth, conn, seq_start=0)
        await client.start()
        writes = 6
        results = await asyncio.wait_for(
            asyncio.gather(*(client.request(b"op %d" % i) for i in range(writes))), 60
        )
        assert all(r and r != b"forged result" for r in results)
        stats = client.reply_checks
        assert stats.acked == writes and len(auth.accepted) == (f + 1) * writes
        # of a write's first f+1 replies at least one came down a forging
        # stream (only f streams are clean), at most all of them
        assert (f + 2) * writes <= stats.checked <= 2 * (f + 1) * writes
        await _stop(client, replicas)

    asyncio.run(run())


def test_a_client_that_takes_replies_on_trust_is_not_saved_by_the_precheck():
    """The control ``replies_unverified``: what wraps the authenticator
    and never asks it accepts the forged replies, verified ahead or not."""

    async def run():
        n, f = 4, 1
        replicas, c_auths, stubs, ledgers = await _cluster(n=n, f=f)
        auth = _Accepted(c_auths[0], verify=False)
        conn = _ForgingConnector(InProcessClientConnector(stubs), n)
        client = new_client(0, n, f, auth, conn, seq_start=0)
        await client.start()
        assert await asyncio.wait_for(client.request(b"trusting"), 30) == b"forged result"
        await _stop(client, replicas)

    asyncio.run(run())


@pytest.mark.parametrize("n, f", [(3, 1), (7, 3), (31, 15)])
def test_a_fault_free_write_costs_f_plus_one_checks(n, f):
    """Not n: a reply that arrives after the quorum is never verified."""

    async def run():
        replicas, c_auths, stubs, ledgers = await _cluster(n=n, f=f)
        client = new_client(
            0, n, f, c_auths[0], InProcessClientConnector(stubs), seq_start=0
        )
        await client.start()
        total_before = replycheck.TOTAL.checked
        writes = 3
        await asyncio.wait_for(
            asyncio.gather(*(client.request(b"w%d" % i) for i in range(writes))), 120
        )
        stats = client.reply_checks
        assert stats.acked == writes
        assert stats.checked == (f + 1) * writes
        assert stats.to_dict()["checks_per_write"] == f + 1
        assert stats.off_lock <= stats.checked
        assert stats.to_dict()["inline"] == stats.checked - stats.off_lock
        assert replycheck.TOTAL.checked - total_before == stats.checked
        await _stop(client, replicas)

    asyncio.run(run())


def test_a_frame_of_replies_is_one_native_call(monkeypatch):
    """Eight writes in flight: a replica's replies to them come in frames
    of several, and each such frame is verified by one call."""
    calls = []
    real = hc.verify_many

    def counted(scheme, items, lib=None):
        calls.append(len(items))
        return real(scheme, items, lib)

    monkeypatch.setattr(hc, "verify_many", counted)

    async def run():
        n, f = 4, 1
        replicas, c_auths, stubs, ledgers = await _cluster(n=n, f=f)
        client = new_client(
            0, n, f, c_auths[0], InProcessClientConnector(stubs), seq_start=0
        )
        await client.start()
        writes = 24
        await asyncio.wait_for(
            asyncio.gather(*(client.request(b"w%d" % i) for i in range(writes))), 60
        )
        stats = client.reply_checks
        assert stats.checked == (f + 1) * writes and stats.acked == writes
        assert calls and min(calls) >= replycheck.MIN_BATCH
        assert (stats.batches, stats.off_lock) == (len(calls), sum(calls))
        assert stats.off_lock <= stats.checked
        assert _checker()._ahead == {}  # every verdict was asked for
        await _stop(client, replicas)

    asyncio.run(run())


def test_a_failed_check_costs_one_more_check():
    """One forged reply ahead of a real one: the quorum waits for one more."""

    async def run():
        n, f = 4, 1
        replicas, c_auths, stubs, ledgers = await _cluster(n=n, f=f)
        auth = _Accepted(c_auths[0])
        conn = _ForgingConnector(InProcessClientConnector(stubs), 1)
        client = new_client(0, n, f, auth, conn, seq_start=0)
        await client.start()
        assert await asyncio.wait_for(client.request(b"one forged"), 30)
        stats = client.reply_checks
        # replica 0's pair counts two checks if it was among the first f+1
        assert stats.acked == 1 and stats.checked in (f + 1, f + 2)
        assert len(auth.accepted) == f + 1
        await _stop(client, replicas)

    asyncio.run(run())


def test_an_authenticator_without_the_seed_call_is_asked_reply_by_reply():
    class _Bare(api.Authenticator):
        """Only the abstract surface, and no forwarding."""

        def __init__(self, inner):
            self._inner = inner
            self.asked = 0

        def generate_message_authen_tag(self, role, msg, audience=-1):
            return self._inner.generate_message_authen_tag(role, msg, audience)

        async def verify_message_authen_tag(self, role, peer_id, msg, tag):
            self.asked += 1
            await self._inner.verify_message_authen_tag(role, peer_id, msg, tag)

    async def run():
        n, f = 4, 1
        replicas, c_auths, stubs, ledgers = await _cluster(n=n, f=f)
        auth = _Bare(c_auths[0])
        client = new_client(
            0, n, f, auth, InProcessClientConnector(stubs), seq_start=0
        )
        await client.start()
        await asyncio.wait_for(
            asyncio.gather(*(client.request(b"w%d" % i) for i in range(8))), 30
        )
        stats = client.reply_checks
        assert (stats.checked, stats.off_lock, stats.batches) == (auth.asked, 0, 0)
        assert stats.to_dict()["inline"] == stats.checked == (f + 1) * 8
        await _stop(client, replicas)

    asyncio.run(run())


def test_group_authenticator_seeds_under_its_own_domain():
    """A grouped client's checks are over prefixed bytes: the seed call
    has to verify what the reply-by-reply call will ask for."""
    from minbft_tpu.groups.runtime import GroupAuthenticator
    from minbft_tpu.sample.authentication import new_test_authenticators

    async def run():
        r_auths, c_auths = new_test_authenticators(3, usig_kind="hmac")
        grouped = GroupAuthenticator(c_auths[0], 2)
        replica = GroupAuthenticator(r_auths[1], 2)
        msgs = [b"reply %d" % i for i in range(3)]
        items = [
            (1, m, replica.generate_message_authen_tag(api.AuthenticationRole.REPLICA, m))
            for m in msgs
        ]
        items[1] = (1, msgs[1], bytes(64))
        checker = replycheck.acquire()
        try:
            role = api.AuthenticationRole.REPLICA
            assert grouped.precheck_message_authen_tags(role, items) == 3
            assert len(checker._ahead) == 3
            await grouped.verify_message_authen_tag(role, *items[0])
            with pytest.raises(api.AuthenticationError):
                await grouped.verify_message_authen_tag(role, *items[1])
            await grouped.verify_message_authen_tag(role, *items[2])
            assert checker._ahead == {}
            # the ungrouped bytes are another check, and an invalid one
            with pytest.raises(api.AuthenticationError):
                await c_auths[0].verify_message_authen_tag(role, *items[0])
        finally:
            replycheck.release(checker)

    asyncio.run(run())


def test_a_native_call_that_raises_leaves_the_checks_to_the_inline_path(
    monkeypatch, caplog
):
    """The seed call decides nothing: its failure is logged, and every
    reply is then verified on its own."""

    def broken(scheme, items, lib=None):
        raise OSError("native verifier outage")

    monkeypatch.setattr(hc, "verify_many", broken)

    async def run():
        n, f = 4, 1
        replicas, c_auths, stubs, ledgers = await _cluster(n=n, f=f)
        client = new_client(
            0, n, f, c_auths[0], InProcessClientConnector(stubs), seq_start=0
        )
        await client.start()
        writes = 16
        await asyncio.wait_for(
            asyncio.gather(*(client.request(b"w%d" % i) for i in range(writes))), 30
        )
        got = client.reply_checks.to_dict()
        assert (got["batches"], got["off_lock"], got["acked"]) == (0, 0, writes)
        assert got["inline"] == got["checked"] == (f + 1) * writes
        await _stop(client, replicas)

    asyncio.run(run())
    assert "reply pre-check failed" in caplog.text


def test_a_checker_that_raises_costs_frames_then_the_stream_not_the_client(
    monkeypatch,
):
    """Both paths down (not a verdict: an outage): the seed call's failure
    decides nothing, each reply's own check then raises against the stream
    that carried it, a run of them redials the stream, the client lives,
    and the write is acknowledged once the outage ends."""
    from minbft_tpu.client.client import _MAX_CONSECUTIVE_REPLY_ERRORS

    n, f = 7, 3
    state = {"raised": 0, "fail": True}
    outage = n * _MAX_CONSECUTIVE_REPLY_ERRORS + n

    def broken(self, scheme, items):
        if state["fail"]:
            raise OSError("verifier backend outage")
        return real_precheck(self, scheme, items)

    real_precheck = replycheck.ReplyChecker.precheck
    monkeypatch.setattr(replycheck.ReplyChecker, "precheck", broken)
    dials: dict = {}

    class _Counting(api.ReplicaConnector):
        def __init__(self, inner):
            self._inner = inner

        def replica_message_stream_handler(self, replica_id):
            inner = self._inner.replica_message_stream_handler(replica_id)

            class _C(api.MessageStreamHandler):
                async def handle_message_stream(self, in_stream):
                    dials[replica_id] = dials.get(replica_id, 0) + 1
                    async for out in inner.handle_message_stream(in_stream):
                        yield out

            return _C()

    async def run():
        replicas, c_auths, stubs, ledgers = await _cluster(n=n, f=f)
        auth = c_auths[0]
        real_verify = auth.verify_message_authen_tag

        async def flaky_verify(role, rid, data, sig):
            if state["fail"] and role == api.AuthenticationRole.REPLICA:
                state["raised"] += 1
                if state["raised"] >= outage:
                    state["fail"] = False
                raise OSError("verifier backend outage")
            return await real_verify(role, rid, data, sig)

        auth.verify_message_authen_tag = flaky_verify
        client = new_client(
            0, n, f, auth, _Counting(InProcessClientConnector(stubs)),
            seq_start=0, retransmit_interval=0.05,
        )
        await client.start()
        results = await asyncio.wait_for(
            asyncio.gather(*(client.request(b"outage %d" % i) for i in range(4))), 60
        )
        assert all(results)
        assert not state["fail"] and state["raised"] >= outage
        assert max(dials.values()) >= 2, dials
        assert client.reply_checks.acked == 4
        await _stop(client, replicas)

    asyncio.run(run())


def test_stop_with_a_write_in_flight_fails_it_and_ends_the_helper_threads():
    lib = hc.native_verifier()

    class _Silent(api.ReplicaConnector):
        def __init__(self, inner):
            self._inner = inner

        def replica_message_stream_handler(self, replica_id):
            class _H(api.MessageStreamHandler):
                async def handle_message_stream(self, in_stream):
                    async for _ in in_stream:
                        pass
                    return
                    yield b""

            return _H()

    async def run():
        n, f = 4, 1
        unhandled = []
        asyncio.get_running_loop().set_exception_handler(
            lambda _loop, ctx: unhandled.append(ctx)
        )
        replicas, c_auths, stubs, ledgers = await _cluster(n=n, f=f)
        client = new_client(
            0, n, f, c_auths[0], _Silent(InProcessClientConnector(stubs)), seq_start=0
        )
        assert _checker() is None
        await client.start()
        if lib is not None:
            assert lib.sigv_pool_threads() == replycheck.HELPERS
        write = asyncio.ensure_future(client.request(b"in flight at stop"))
        await asyncio.sleep(0.05)
        await client.stop()
        with pytest.raises(ConnectionError):
            await asyncio.wait_for(write, 5)
        assert _checker() is None
        if lib is not None:
            assert lib.sigv_pool_threads() == 0
        for r in replicas:
            await r.stop()
        await asyncio.sleep(0)
        assert not unhandled, unhandled

    asyncio.run(run())


def test_clients_of_one_loop_share_the_checker_and_the_last_lets_go():
    async def run():
        replicas, c_auths, stubs, ledgers = await _cluster(n=4, f=1, n_clients=2)
        clients = [
            new_client(c, 4, 1, c_auths[c], InProcessClientConnector(stubs), seq_start=0)
            for c in range(2)
        ]
        for c in clients:
            await c.start()
        checker = _checker()
        assert checker._users == 2
        await asyncio.wait_for(
            asyncio.gather(*(c.request(b"shared") for c in clients)), 30
        )
        await clients[0].stop()
        assert _checker() is checker and checker.off_lock
        assert await asyncio.wait_for(clients[1].request(b"still served"), 30)
        await clients[1].stop()
        assert _checker() is None
        assert [c.reply_checks.acked for c in clients] == [1, 2]
        for r in replicas:
            await r.stop()

    asyncio.run(run())


def test_without_the_native_module_every_check_is_inline_and_the_counter_says_so(
    monkeypatch,
):
    monkeypatch.setattr(hc, "native_verifier", lambda: None)

    async def run():
        n, f = 4, 1
        replicas, c_auths, stubs, ledgers = await _cluster(n=n, f=f)
        client = new_client(
            0, n, f, c_auths[0], InProcessClientConnector(stubs), seq_start=0
        )
        await client.start()
        assert not _checker().off_lock
        writes = 12
        await asyncio.wait_for(
            asyncio.gather(*(client.request(b"w%d" % i) for i in range(writes))), 30
        )
        got = client.reply_checks.to_dict()
        assert (got["batches"], got["off_lock"]) == (0, 0)
        assert got["inline"] == got["checked"] == (f + 1) * writes
        await _stop(client, replicas)

    asyncio.run(run())


def test_reply_checks_reach_the_timeline_and_the_trace_dump(tmp_path, monkeypatch):
    from minbft_tpu.obs import trace as obs_trace

    monkeypatch.setenv(obs_trace.TRACE_DUMP_ENV, str(tmp_path / "t"))

    async def run():
        before = obs_trace.timeline()["reply_checks"]
        replicas, c_auths, stubs, ledgers = await _cluster(n=4, f=1)
        client = new_client(
            0, 4, 1, c_auths[0], InProcessClientConnector(stubs), seq_start=0
        )
        await client.start()
        await asyncio.wait_for(client.request(b"dumped"), 30)
        await _stop(client, replicas)
        after = obs_trace.timeline()["reply_checks"]
        assert after["checked"] - before["checked"] == 2
        assert after["acked"] - before["acked"] == 1
        assert set(after) == {
            "batches", "checked", "off_lock", "inline", "acked", "checks_per_write",
        }

    asyncio.run(run())
    doc = json.loads((tmp_path / "t.c0.json").read_text())
    assert doc["reply_checks"]["checked"] == 2
    assert doc["reply_checks"]["checks_per_write"] == 2
