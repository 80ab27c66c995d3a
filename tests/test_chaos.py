"""Chaos suite: deterministic fault injection, Byzantine adversaries,
and the seeded n=4/f=1 chaos soak (ISSUE 5).

Seed discipline: every seeded test resolves its seed via
``testing.faultnet.chaos_seed`` — ``MINBFT_CHAOS_SEED`` in the
environment wins (CI pins one; export it to replay a failure), otherwise
the test's committed default.  Failures print the seed.  The fault
schedule is a pure function of (seed, link, frame index):
``test_same_seed_reproduces_fault_schedule`` pins byte-identical replay,
and the soak cross-checks its live census against
``FaultNet.replay_counts`` recomputed from the seed alone.
"""

import asyncio
import json
import logging
import os
import sys

import pytest

from conftest import ledgers_reach, make_cluster
from minbft_tpu.client import new_client
from minbft_tpu.messages import Commit, Request
from minbft_tpu.sample.config import SimpleConfiger
from minbft_tpu.sample.conn.inprocess import InProcessClientConnector
from minbft_tpu.testing import (
    FaultNet,
    FaultPlan,
    InvariantChecker,
    chaos_seed,
)
from minbft_tpu.testing.adversary import Adversary, ConflictingReplyReplica


# Dev mode (PYTHONDEVMODE — the CI chaos step) arms asyncio debug mode,
# which captures a source traceback on EVERY Task/Future creation and
# times every callback: the protocol hot path runs roughly an order of
# magnitude slower, so a cluster tuned to sub-second patience knobs
# livelocks in view-change thrash (each round outlives timeout_request,
# every request demands a new view, forever).  The seeded fault schedule
# is FRAME-indexed, not time-based, so stretching every wall-clock knob
# by one factor keeps replay byte-identical — same draws, same per-kind
# census — while giving the slowed cluster proportionate patience.
TIME_SCALE = 5.0 if sys.flags.dev_mode else 1.0


def _t(seconds: float) -> float:
    """A wall-clock knob (protocol timeout, retransmit interval, test
    deadline) scaled for the execution mode."""
    return seconds * TIME_SCALE


# Phase markers interleave with the replicas' own captured log lines on
# failure — without them a wedge's log reads as one undifferentiated
# stream of timeouts with no way to tell which phase wedged.
_log = logging.getLogger("minbft.chaos")


# ---------------------------------------------------------------------------
# faultnet unit layer: the determinism contract.


def _frames(n, tag=b"fr"):
    return [tag + b"-%06d" % i + bytes([i % 251]) * (i % 17) for i in range(n)]


async def _pump(net, src, dst, frames):
    async def gen():
        for fr in frames:
            yield fr

    out = []
    async for fr in net.pipe(src, dst, gen()):
        out.append(fr)
    return out


def test_same_seed_reproduces_fault_schedule():
    """Two independent FaultNets with the SAME seed apply byte-identical
    faults to the same frame sequence (the MINBFT_CHAOS_SEED replay
    contract); a different seed produces a different schedule."""
    plan = FaultPlan(
        drop=0.1, delay=0.2, delay_s=(0.0, 0.0005), duplicate=0.1,
        reorder=0.15, corrupt=0.1, reset=0.004,
    )
    frames = _frames(400)

    async def run(seed):
        net = FaultNet(seed=seed, default_plan=plan)
        out = await _pump(net, "a", "b", frames)
        return out, net.census.seeded_counts(), dict(net.census.frames)

    out1, census1, frames1 = asyncio.run(run(1234))
    out2, census2, frames2 = asyncio.run(run(1234))
    assert out1 == out2
    assert census1 == census2
    assert frames1 == frames2
    assert sum(census1.values()) > 0  # the schedule actually fired
    out3, census3, _ = asyncio.run(run(99))
    assert (out3, census3) != (out1, census1)


def test_replay_counts_matches_live_census():
    """replay_counts recomputes a live run's seeded injection counts from
    (seed, per-link frame counts) alone — fresh RNGs, no live state."""
    plan = FaultPlan(
        drop=0.08, delay=0.1, delay_s=(0.0, 0.0002), duplicate=0.06,
        reorder=0.1, corrupt=0.05, reset=0.01,
    )

    async def run():
        net = FaultNet(seed=77, default_plan=plan)
        for src, dst, n in (("a", "b", 300), ("b", "a", 200), ("c", "a", 120)):
            await _pump(net, src, dst, _frames(n))
        return net

    net = asyncio.run(run())
    live = net.census.seeded_counts()
    assert net.replay_counts() == live
    assert net.replay_counts(dict(net.census.frames), plan=plan) == live


def test_faultnet_stall_partition_and_census_exposition():
    """Scripted faults: a stalled link holds frames without ending the
    stream (and releases them on unstall); a partition drops cross-group
    frames until healed; the census renders through the Prometheus
    exposition (obs.collect_faultnet)."""

    async def run():
        net = FaultNet(seed=5)

        async def gen():
            for i in range(6):
                yield b"f%d" % i

        got = []

        async def consume():
            async for fr in net.pipe("r0", "r1", gen()):
                got.append(fr)

        net.stall(src="r0")
        task = asyncio.ensure_future(consume())
        await asyncio.sleep(0.15)
        assert got == []  # held, stream still open
        net.unstall(src="r0")
        await asyncio.wait_for(task, 5)
        assert got == [b"f%d" % i for i in range(6)]
        assert net.census.counters.get("stall", 0) >= 1

        net.partition({"r0", "r1"}, {"r2", "r3"})
        cross = await _pump(net, "r0", "r2", [b"x", b"y"])
        same = await _pump(net, "r0", "r1", [b"z"])
        assert cross == [] and same == [b"z"]
        assert net.census.counters.get("partition", 0) == 2
        net.heal_partition()
        assert await _pump(net, "r0", "r2", [b"x2"]) == [b"x2"]

        from minbft_tpu.obs import collect_faultnet, render_families

        text = render_families(collect_faultnet(net.census))
        assert 'minbft_faultnet_injected_total{kind="stall"}' in text
        assert 'minbft_faultnet_injected_total{kind="partition"} 2' in text
        assert "minbft_faultnet_frames_total" in text
        return True

    assert asyncio.run(run())


def test_reset_all_ends_live_streams():
    async def run():
        net = FaultNet(seed=3)
        started = asyncio.Event()

        async def endless():
            yield b"one"
            started.set()
            await asyncio.sleep(60)

        got = []

        async def consume():
            async for fr in net.pipe("a", "b", endless()):
                got.append(fr)

        task = asyncio.ensure_future(consume())
        await asyncio.wait_for(started.wait(), 5)
        net.reset_all()
        await asyncio.wait_for(task, 5)  # the idle stream ended promptly
        assert got == [b"one"]
        assert net.census.counters.get("reset_all", 0) == 1
        return True

    assert asyncio.run(run())


# ---------------------------------------------------------------------------
# Byzantine adversary suite: real keys, real codec, hostile content.
# Every behavior must be rejected with no safety-invariant violation AND
# the cluster must still commit the honest workload.


def _short_cfg(vc=3.0):
    return SimpleConfiger(
        n=4, f=1, timeout_request=_t(0.8), timeout_prepare=_t(0.4),
        timeout_viewchange=_t(vc),
    )


def test_adversary_equivocation_rejected():
    """A Byzantine PRIMARY certifies one PREPARE, then re-sends the same
    UI over different content.  USIG counter monotonicity is the paper's
    core defense: one counter certifies ONE message, so the copy's cert
    cannot verify — backups must drop it, and the cluster (having lost
    only its primary to the adversary, within f=1) must view-change and
    keep committing."""

    async def run():
        replicas, c_auths, stubs, ledgers = await make_cluster(cfg=_short_cfg())
        client = new_client(0, 4, 1, c_auths[0], InProcessClientConnector(stubs))
        await client.start()
        accepted = []
        r0 = await asyncio.wait_for(client.request(b"equiv-seed"), 30)
        accepted.append((b"equiv-seed", r0))
        await ledgers_reach(ledgers, 1)

        # A genuine client-signed request to re-batch (from replica 1's
        # own COMMIT, which embeds the primary's PREPARE).
        commits = [
            m for m in replicas[1].handlers.message_log.snapshot()
            if isinstance(m, Commit)
        ]
        req = commits[0].prepare.requests[0]

        # The primary turns adversarial: its honest process stops, its
        # keys keep signing.
        stubs[0].crash()
        await replicas[0].stop()
        adv = Adversary(0, replicas[0].handlers.authenticator, 4)
        evil = Request(
            client_id=req.client_id, seq=req.seq + 999,
            operation=b"equiv-evil", signature=b"\x00" * 64,
        )
        pa, pb = adv.equivocating_prepares(0, [req], [evil])
        assert pb.ui.counter == pa.ui.counter  # the equivocation attempt

        m1 = replicas[1].metrics
        dropped = m1.counters.get("messages_dropped", 0)
        applied = m1.counters.get("prepares_accepted", 0)
        await adv.inject(stubs[1].peer_message_stream_handler(), [pa, pb])
        for _ in range(100):
            if m1.counters.get("messages_dropped", 0) > dropped:
                break
            await asyncio.sleep(0.02)
        # the conflicting copy is DROPPED (cert forgery)...
        assert m1.counters.get("messages_dropped", 0) >= dropped + 1
        # ...while at most the first certification was accepted.
        assert m1.counters.get("prepares_accepted", 0) <= applied + 1
        # nothing executed twice, nothing evil executed
        assert all(lg.length == 1 for lg in ledgers[1:])

        # honest workload continues (view change deposes the adversary)
        r1 = await asyncio.wait_for(client.request(b"after-equiv"), 45)
        accepted.append((b"after-equiv", r1))
        await ledgers_reach(ledgers[1:], len(accepted), timeout=_t(30))
        InvariantChecker(replicas, ledgers, correct=(1, 2, 3)).check(accepted)

        await client.stop()
        for r in replicas[1:]:
            await r.stop()
        return True

    assert asyncio.run(run())


def test_adversary_stale_replay_wrong_view_and_counter_gap():
    """Three adversarial behaviors from a backup's genuine keys:

    - stale-UI replay → dedup'd by once-only in-order capture (handled,
      no re-execution);
    - wrong-view PREPARE (genuinely certified, view the cluster is not
      in) → captured then refused, never applied;
    - counter-gap COMMIT (genuine cert, one counter burned unsent) →
      parked at capture, never processed past the gap.

    Throughout: the cluster keeps committing the honest workload."""

    async def run():
        replicas, c_auths, stubs, ledgers = await make_cluster(
            cfg=_short_cfg(vc=0.5)
        )
        client = new_client(0, 4, 1, c_auths[0], InProcessClientConnector(stubs))
        await client.start()
        accepted = []
        r0 = await asyncio.wait_for(client.request(b"adv-seed"), 30)
        accepted.append((b"adv-seed", r0))
        await ledgers_reach(ledgers, 1)

        # Replica 2 turns adversarial (still within f=1).
        genuine_commit = next(
            m for m in replicas[2].handlers.message_log.snapshot()
            if isinstance(m, Commit)
        )
        stubs[2].crash()
        await replicas[2].stop()
        adv = Adversary(2, replicas[2].handlers.authenticator, 4)

        # -- stale-UI replay at replica 1
        m1 = replicas[1].metrics
        handled = m1.counters.get("messages_handled", 0)
        await adv.inject(
            stubs[1].peer_message_stream_handler(),
            [adv.replay(genuine_commit)] * 3,
        )
        for _ in range(100):
            if m1.counters.get("messages_handled", 0) >= handled + 3:
                break
            await asyncio.sleep(0.02)
        assert m1.counters.get("messages_handled", 0) >= handled + 3
        assert ledgers[1].length == 1  # no double execution

        # -- wrong-view PREPARE at replica 1 (adversary IS view 2's
        # primary, but the cluster is in view 0)
        applied = m1.counters.get("prepares_accepted", 0)
        wv = adv.wrong_view_prepare(2, [genuine_commit.prepare.requests[0]])
        # the future-view park expires after 2*max(vc_timeout, 1.0)
        # (2s at this cfg, 5s dev-mode-scaled), then the message must be
        # captured and REFUSED, not applied — hold past the expiry
        await adv.inject(
            stubs[1].peer_message_stream_handler(), [wv],
            hold_s=2.0 * max(_t(0.5), 1.0) + _t(1.5),
        )
        assert m1.counters.get("messages_dropped_future_view", 0) >= 1
        assert m1.counters.get("prepares_accepted", 0) == applied
        assert ledgers[1].length == 1

        # -- counter-gap COMMIT at replica 3
        gap_commit = adv.counter_gap_commit(genuine_commit.prepare)
        m3 = replicas[3].metrics
        counted = m3.counters.get("commitments_counted", 0)
        mark_before = replicas[3].handlers.peer_states.peer(2)._next_cv
        assert gap_commit.ui.counter > mark_before + 1  # a real gap
        await adv.inject(stubs[3].peer_message_stream_handler(), [gap_commit])
        # parked at capture: the watermark must NOT have advanced to (or
        # past) the gapped counter, and no commitment was counted for it
        assert replicas[3].handlers.peer_states.peer(2)._next_cv <= mark_before + 1
        assert m3.counters.get("commitments_counted", 0) == counted
        assert ledgers[3].length == 1

        # honest workload still commits (primary 0 is honest and alive)
        r1 = await asyncio.wait_for(client.request(b"adv-after"), 30)
        accepted.append((b"adv-after", r1))
        await ledgers_reach((ledgers[0], ledgers[1], ledgers[3]), len(accepted))
        InvariantChecker(replicas, ledgers, correct=(0, 1, 3)).check(accepted)

        await client.stop()
        for i in (0, 1, 3):
            await replicas[i].stop()
        return True

    assert asyncio.run(run())


def test_adversary_conflicting_replies_stay_below_quorum():
    """A replica answering clients with correctly-SIGNED wrong results:
    one liar's vote must never complete the client's f+1 matching-reply
    quorum, and the accepted result must be the honest ledgers' digest."""

    async def run():
        replicas, c_auths, stubs, ledgers = await make_cluster()
        # replica 2's identity is taken over by the reply forger
        stubs[2].crash()
        await replicas[2].stop()
        adv = Adversary(2, replicas[2].handlers.authenticator, 4)
        forger = ConflictingReplyReplica(adv)
        stubs[2].revive()
        stubs[2].assign_replica(forger)

        client = new_client(0, 4, 1, c_auths[0], InProcessClientConnector(stubs))
        await client.start()
        res = await asyncio.wait_for(client.request(b"honest-op"), 30)
        assert res != forger.forged_result
        for _ in range(200):
            if forger.replies_sent >= 1:
                break
            await asyncio.sleep(0.02)
        assert forger.replies_sent >= 1  # the liar really voted
        await ledgers_reach((ledgers[0], ledgers[1], ledgers[3]), 1)
        assert res == ledgers[0].block(1).digest()
        InvariantChecker(replicas, ledgers, correct=(0, 1, 3)).check(
            [(b"honest-op", res)]
        )

        await client.stop()
        for i in (0, 1, 3):
            await replicas[i].stop()
        return True

    assert asyncio.run(run())


# ---------------------------------------------------------------------------
# View change under message LOSS (satellite): the transition completes
# across lossy links, not just after clean crashes.


def test_view_change_completes_under_message_loss():
    seed = chaos_seed(default=0xA11CE)

    async def run():
        net = FaultNet(
            seed=seed,
            default_plan=FaultPlan(
                drop=0.05, delay=0.15, delay_s=(0.0005, 0.008),
                duplicate=0.05, reorder=0.08, reset=0.01,
            ),
        )
        cfg = SimpleConfiger(
            n=4, f=1, timeout_request=_t(0.8), timeout_prepare=_t(0.4),
            timeout_viewchange=_t(1.5),
        )
        replicas, c_auths, stubs, ledgers = await make_cluster(
            cfg=cfg, wrap_conn=lambda i, c: net.wrap(c, f"r{i}")
        )
        client = new_client(
            0, 4, 1, c_auths[0], InProcessClientConnector(stubs),
            retransmit_interval=_t(0.5),
        )
        await client.start()
        accepted = []
        r0 = await asyncio.wait_for(client.request(b"loss-seed"), _t(60))
        accepted.append((b"loss-seed", r0))

        stubs[0].crash()
        await replicas[0].stop()

        # REQ-VIEW-CHANGE / VIEW-CHANGE / NEW-VIEW now cross lossy links;
        # the timeout/escalation + redial-replay paths must still land a
        # completed transition.
        r1 = await asyncio.wait_for(client.request(b"loss-after-crash"), _t(90))
        accepted.append((b"loss-after-crash", r1))
        for r in replicas[1:]:
            cur, _ = await r.handlers.view_state.hold_view()
            assert cur >= 1, f"replica {r.id} still in view {cur}"
        await ledgers_reach(ledgers[1:], 2, timeout=_t(30))
        InvariantChecker(replicas, ledgers, correct=(1, 2, 3)).check(accepted)
        assert net.census.counters.get("drop", 0) >= 1

        await client.stop()
        for r in replicas[1:]:
            await r.stop()
        return True

    try:
        assert asyncio.run(run())
    except BaseException:
        print(f"replay with MINBFT_CHAOS_SEED={seed}")
        raise


# ---------------------------------------------------------------------------
# Stalled (half-open) primary: frames stop, connections stay up — the
# request-timeout → view-change path must fire on BOTH transports (a
# closed connection is the easy case the old tests covered).


def test_stalled_primary_triggers_view_change_inprocess():
    async def run():
        net = FaultNet(seed=chaos_seed(default=0x57A11))
        replicas, c_auths, stubs, ledgers = await make_cluster(
            cfg=_short_cfg(), wrap_conn=lambda i, c: net.wrap(c, f"r{i}")
        )
        client = new_client(
            0, 4, 1, c_auths[0],
            net.wrap(InProcessClientConnector(stubs), "c0"),
            retransmit_interval=0.5,
        )
        await client.start()
        accepted = []
        r0 = await asyncio.wait_for(client.request(b"stall-seed"), 30)
        accepted.append((b"stall-seed", r0))

        net.stall_replica(0)  # half-open: streams stay up, frames stop
        r1 = await asyncio.wait_for(client.request(b"stall-after"), 60)
        accepted.append((b"stall-after", r1))
        for r in replicas[1:]:
            cur, _ = await r.handlers.view_state.hold_view()
            assert cur >= 1, f"replica {r.id} still in view {cur}"
        assert net.census.counters.get("stall", 0) >= 1
        net.unstall_replica(0)
        # committed-results is a convergence property (f+1 replies prove
        # only f+1 executions) — give laggards a bounded catch-up first.
        await ledgers_reach(ledgers[1:], len(accepted), timeout=_t(30))
        InvariantChecker(replicas, ledgers, correct=(1, 2, 3)).check(accepted)

        await client.stop()
        for r in replicas:
            await r.stop()
        return True

    assert asyncio.run(run())


def test_stalled_primary_triggers_view_change_tcp():
    """Same half-open primary scenario over the native TCP transport:
    replica stubs behind TcpReplicaServer, dial-side TcpReplicaConnectors
    wrapped in the FaultNet, idle teardown armed."""

    async def run():
        from minbft_tpu.core import new_replica
        from minbft_tpu.sample.authentication import new_test_authenticators
        from minbft_tpu.sample.conn.inprocess import make_testnet_stubs
        from minbft_tpu.sample.conn.tcp import (
            TcpReplicaConnector,
            TcpReplicaServer,
            connect_many_replicas_tcp,
        )
        from minbft_tpu.sample.requestconsumer import SimpleLedger

        net = FaultNet(seed=chaos_seed(default=0x7C9))
        n, f = 4, 1
        cfg = _short_cfg()
        r_auths, c_auths = new_test_authenticators(n, usig_kind="hmac")
        stubs = make_testnet_stubs(n)
        servers = {}
        addrs = {}
        for i in range(n):
            srv = TcpReplicaServer(stubs[i])
            addrs[i] = await srv.start("127.0.0.1:0")
            servers[i] = srv
        ledgers = [SimpleLedger() for _ in range(n)]
        replicas = []
        for i in range(n):
            conn = TcpReplicaConnector("peer", idle_timeout=30.0)
            for j, addr in addrs.items():
                if j != i:
                    conn.connect_replica(j, addr)
            r = new_replica(i, cfg, r_auths[i], net.wrap(conn, f"r{i}"), ledgers[i])
            stubs[i].assign_replica(r)
            replicas.append(r)
        for r in replicas:
            await r.start()
        client_conn = connect_many_replicas_tcp(addrs, kind="client")
        client = new_client(
            0, n, f, c_auths[0], net.wrap(client_conn, "c0"),
            retransmit_interval=0.5,
        )
        await client.start()
        try:
            accepted = []
            r0 = await asyncio.wait_for(client.request(b"tcp-stall-seed"), 60)
            accepted.append((b"tcp-stall-seed", r0))

            net.stall_replica(0)
            r1 = await asyncio.wait_for(client.request(b"tcp-stall-after"), 90)
            accepted.append((b"tcp-stall-after", r1))
            for r in replicas[1:]:
                cur, _ = await r.handlers.view_state.hold_view()
                assert cur >= 1, f"replica {r.id} still in view {cur}"
            assert net.census.counters.get("stall", 0) >= 1
            net.unstall_replica(0)
            # committed-results is a convergence property — wait for the
            # correct laggards before holding every ledger to it.
            await ledgers_reach(ledgers[1:], len(accepted), timeout=_t(30))
            InvariantChecker(replicas, ledgers, correct=(1, 2, 3)).check(accepted)
        finally:
            await client.stop()
            for r in replicas:
                await r.stop()
            for srv in servers.values():
                await srv.stop()
            await client_conn.close()
        return True

    assert asyncio.run(run())


def test_tcp_idle_timeout_recovers_half_open_stream():
    """Satellite: the native TCP connector's per-stream read-idle timeout
    tears down a half-open connection (server alive, frames stalled by a
    faultnet stall BELOW the dialer's socket) so the redial loop can
    recover — without it the read parks forever."""
    from minbft_tpu import api
    from minbft_tpu.sample.conn.tcp import TcpReplicaConnector, TcpReplicaServer
    from minbft_tpu.testing import FaultyConnectionHandler

    class _Echo(api.MessageStreamHandler):
        async def handle_message_stream(self, in_stream):
            async for data in in_stream:
                yield b"E:" + data

    class _EchoConn(api.ConnectionHandler):
        def peer_message_stream_handler(self):
            return _Echo()

        def client_message_stream_handler(self):
            return _Echo()

    async def run():
        net = FaultNet(seed=1)
        server = TcpReplicaServer(FaultyConnectionHandler(_EchoConn(), net, "srv"))
        addr = await server.start("127.0.0.1:0")
        conn = TcpReplicaConnector("peer", idle_timeout=0.4)
        conn.connect_replica(0, addr)
        try:
            handler = conn.replica_message_stream_handler(0)
            sent = asyncio.Event()

            async def outgoing():
                yield b"one"
                await sent.wait()
                yield b"two"
                await asyncio.sleep(60)

            out = handler.handle_message_stream(outgoing())
            assert await asyncio.wait_for(out.__anext__(), 10) == b"E:one"
            # Stall the server side: the TCP connection stays up but no
            # frames flow — the dialer's idle deadline must END the
            # stream (the redial loop's recovery signal)...
            net.stall(dst="srv")
            sent.set()
            t0 = asyncio.get_running_loop().time()
            with pytest.raises(StopAsyncIteration):
                await asyncio.wait_for(out.__anext__(), 10)
            assert asyncio.get_running_loop().time() - t0 < 5.0
            await out.aclose()
            # ...and after the stall heals, a fresh dial works again.
            net.unstall(dst="srv")
            h2 = conn.replica_message_stream_handler(0)

            async def once():
                yield b"back"
                await asyncio.sleep(60)

            out2 = h2.handle_message_stream(once())
            assert await asyncio.wait_for(out2.__anext__(), 10) == b"E:back"
            await out2.aclose()
        finally:
            await server.stop()
            await conn.close()
        return True

    assert asyncio.run(run())


# ---------------------------------------------------------------------------
# Silent tail loss: the hardest liveness hole a lossy link can open.  A
# replica that misses a burst's TAIL (a partition swallowing commits, a
# dropped NEW-VIEW with no follow-on traffic) has NOTHING to react to:
# no counter gap parks (nothing later arrived), no stream ends, no
# timeout fires.  Recovery is the dial loop's idle-refresh — tear down a
# silent stream and redial with a resumable HELLO so the publisher
# replays just the missed tail.


def test_idle_refresh_heals_silent_tail_loss():
    async def run():
        net = FaultNet(seed=chaos_seed(default=0x1D7E))  # faithful plan
        cfg = SimpleConfiger(
            n=4, f=1, timeout_request=_t(60.0), timeout_prepare=_t(30.0),
            timeout_viewchange=_t(1.0),
        )
        replicas, c_auths, stubs, ledgers = await make_cluster(
            cfg=cfg, wrap_conn=lambda i, c: net.wrap(c, f"r{i}")
        )
        client = new_client(
            0, 4, 1, c_auths[0],
            net.wrap(InProcessClientConnector(stubs), "c0"),
        )
        await client.start()
        accepted = []
        try:
            r0 = await asyncio.wait_for(client.request(b"tail-seed"), _t(30))
            accepted.append((b"tail-seed", r0))
            await ledgers_reach(ledgers, 1, timeout=_t(15))

            # r3 alone on the wrong side; the client stays with the
            # majority so NOTHING reaches r3 from here on.
            net.partition({"r0", "r1", "r2", "c0"}, {"r3"})
            for i in range(3):
                op = b"tail-%d" % i
                res = await asyncio.wait_for(client.request(op), _t(30))
                accepted.append((op, res))
            assert ledgers[3].length == 1  # r3 really missed the burst

            # Heal — and issue NO further traffic.  Without the
            # idle-refresh this wedges forever: the partition dropped
            # frames on streams that stayed up, so r3 sees only silence.
            net.heal_partition()
            # (a timeout here: idle-refresh did not deliver the tail)
            await ledgers_reach(ledgers[3:], len(accepted), timeout=_t(45))
            assert replicas[3].metrics.counters.get("idle_redials", 0) >= 1
            InvariantChecker(replicas, ledgers).check(accepted)
        finally:
            await client.stop()
            for r in replicas:
                await r.stop()
        return True

    assert asyncio.run(run())


# ---------------------------------------------------------------------------
# THE chaos soak: n=4/f=1 under seeded drop+delay+duplicate+reorder+
# corrupt(+reset), one partition-and-heal, one primary stall — 100% of
# issued requests must commit, invariants must hold on every replica,
# and the live census must match the schedule recomputed from the seed.


# Per-frame fault probabilities.  Calibrated to the BUNDLE-ingest frame
# dynamics: the batch runtime coalesces harder (one transport frame now
# carries a whole drained bundle), so the soak sees roughly half the
# seeded frames the per-task runtime did — ~90-110 on this container.
# corrupt at the old 0.008 had E[corrupt] ~ 0.7 there and legitimately
# came up zero; the raised rates also exercise corrupt's bigger blast
# radius (one flipped byte now rejects a whole coalesced bundle at
# split_multi), which the retransmit/replay paths must — and do —
# absorb.  The per-kind `>= 1 injected` assertion additionally gates on
# expected count at the observed frame volume (see the soak), so
# run-to-run frame-count swings can never turn a fair zero into a flake.
CHAOS_PLAN = FaultPlan(
    drop=0.03,
    delay=0.10,
    delay_s=(0.0005, 0.008),
    duplicate=0.03,
    reorder=0.05,
    corrupt=0.025,
    reset=0.004,
)


def test_chaos_soak_commits_under_faults():
    seed = chaos_seed(default=0xC4A05)

    async def run():
        net = FaultNet(seed=seed, default_plan=CHAOS_PLAN)
        cfg = SimpleConfiger(
            n=4, f=1, timeout_request=_t(0.8), timeout_prepare=_t(0.4),
            timeout_viewchange=_t(1.0),
        )
        replicas, c_auths, stubs, ledgers = await make_cluster(
            cfg=cfg, wrap_conn=lambda i, c: net.wrap(c, f"r{i}")
        )
        checker = InvariantChecker(replicas, ledgers)
        client = new_client(
            0, 4, 1, c_auths[0],
            net.wrap(InProcessClientConnector(stubs), "c0"),
            retransmit_interval=_t(0.4), max_inflight=8,
        )
        await client.start()
        accepted = []

        async def issue(tag, k, timeout=90):
            ops = [b"chaos-%s-%d" % (tag, i) for i in range(k)]
            results = await asyncio.gather(
                *[client.request(op, timeout=_t(timeout)) for op in ops]
            )
            accepted.extend(zip(ops, results))

        try:
            # Phase A: seeded chaos only (drop/delay/dup/reorder/corrupt).
            _log.warning("chaos phase A: 8 requests under seeded plan")
            await issue(b"a", 8)
            # Invariants hold MID-run: prefix consistency and UI
            # integrity are instant properties.  Committed-results is a
            # CONVERGENCE property (f+1 replies prove only f+1 replicas
            # executed; the rest legitimately lag under chaos), so give
            # the laggards a bounded catch-up before holding every
            # ledger to the accepted set.
            checker.check()
            await ledgers_reach(ledgers, len(accepted), timeout=45)
            checker.check(accepted)

            # Phase B: partition {r0,r1} | {r2,r3} while traffic flows
            # (the majority-side primary keeps committing), then heal.
            _log.warning("chaos phase B: partition {r0,r1}|{r2,r3} + 6 requests")
            net.partition({"r0", "r1"}, {"r2", "r3"})
            issue_b = asyncio.ensure_future(issue(b"b", 6))
            await asyncio.sleep(1.5)
            net.heal_partition()
            _log.warning("chaos phase B: partition healed")
            t_heal = asyncio.get_running_loop().time()
            await issue_b
            # Recovery latency: heal → every partition-spanning request
            # client-accepted (the perf/CHAOS.md census headline).
            recovery_after_heal_s = (
                asyncio.get_running_loop().time() - t_heal
            )

            # Let the post-partition view settle cluster-wide before
            # picking the primary to stall.
            deadline = asyncio.get_running_loop().time() + 30
            view = 0
            while asyncio.get_running_loop().time() < deadline:
                views = []
                for r in replicas:
                    cur, _ = await r.handlers.view_state.hold_view()
                    views.append(cur)
                if len(set(views)) == 1:
                    view = views[0]
                    break
                await asyncio.sleep(0.1)

            # Phase C: stall the CURRENT primary (half-open — streams
            # stay connected, frames stop) → request timeouts must
            # depose it and commits continue in the next view.
            primary = view % 4
            _log.warning(
                "chaos phase C: settled view %d, stalling primary r%d",
                view, primary,
            )
            net.stall_replica(primary)
            await issue(b"c", 6)
            # Commits resume with the new primary + one backup (f+1), so
            # the third survivor may legitimately still be applying the
            # NEW-VIEW when the batch resolves — poll, don't snapshot.
            survivors = [r for r in replicas if r.id != primary]
            deadline = asyncio.get_running_loop().time() + _t(30)
            views = {}
            while asyncio.get_running_loop().time() < deadline:
                for r in survivors:
                    cur, _ = await r.handlers.view_state.hold_view()
                    views[r.id] = cur
                if all(v > view for v in views.values()):
                    break
                await asyncio.sleep(0.05)
            assert all(v > view for v in views.values()), (
                f"survivors still at {views} (stalled primary {primary} "
                f"not deposed past view {view})"
            )
            net.unstall_replica(primary)

            # Freeze the seeded census NOW (heal clears the plan, and
            # post-heal frames draw from the zero plan).
            frames_snapshot = dict(net.census.frames)
            live_seeded = dict(net.census.seeded_counts())

            # Phase D: heal + reset every stream (redials replay full
            # logs — the convergence step), then a clean tail batch.
            _log.warning("chaos phase D: heal + reset_all + 4 requests")
            net.heal()
            net.reset_all()
            await issue(b"d", 4, timeout=60)

            # 100% of issued requests committed...
            assert len(accepted) == 24
            assert all(res for _, res in accepted)
            # ...on EVERY replica (the stalled ex-primary catches up).
            await ledgers_reach(ledgers, len(accepted), timeout=60)

            # Safety invariants across ALL replicas at teardown.
            summary = checker.check(accepted)
            assert summary["accepted_checked"] == 24

            # The faults really happened... asserted per kind only when
            # its EXPECTED count at the run's observed frame volume makes
            # a zero impossible-in-practice (E >= 5 -> P(zero) < 1%).
            # Frame volume is timing-dependent (bundle coalescing, host
            # load): a quiet run legitimately draws zero events of a
            # low-probability kind, and that is the seeded schedule
            # working, not a missing fault injector — the determinism
            # cross-check below (replayed == live) covers those kinds
            # exactly.  High-volume runs (CI's full-size soak) clear the
            # gate for every kind and keep the assertion's full strength.
            seeded_frames = sum(frames_snapshot.values())
            for kind, p in (
                ("drop", CHAOS_PLAN.drop),
                ("delay", CHAOS_PLAN.delay),
                ("duplicate", CHAOS_PLAN.duplicate),
                ("reorder", CHAOS_PLAN.reorder),
                ("corrupt", CHAOS_PLAN.corrupt),
            ):
                if seeded_frames * p >= 5.0:
                    assert net.census.counters.get(kind, 0) >= 1, (
                        kind, seeded_frames, net.census.counters)
            assert net.census.counters.get("stall", 0) >= 1
            assert net.census.counters.get("partition", 0) >= 1
            # ...and followed the seed's deterministic schedule exactly:
            # the same MINBFT_CHAOS_SEED + the same frame counts always
            # reproduce these per-kind injection counts.
            replayed = net.replay_counts(frames_snapshot, plan=CHAOS_PLAN)
            assert replayed == live_seeded, (replayed, live_seeded)
            out = net.census.snapshot()
            out["seed"] = seed
            out["time_scale"] = TIME_SCALE
            out["requests_committed"] = len(accepted)
            out["recovery_after_heal_s"] = round(recovery_after_heal_s, 3)
            return out
        finally:
            await client.stop()
            for r in replicas:
                await r.stop()

    try:
        census = asyncio.run(run())
    except BaseException:
        print(f"replay with MINBFT_CHAOS_SEED={seed}")
        raise
    assert census["frames_total"] > 0
    # perf/CHAOS.md records one committed census; regenerate it with
    # MINBFT_CHAOS_CENSUS=<path> pointing at a JSON dump target.
    census_path = os.environ.get("MINBFT_CHAOS_CENSUS")
    if census_path:
        with open(census_path, "w") as fh:
            json.dump(census, fh, indent=2, sort_keys=True)
            fh.write("\n")
