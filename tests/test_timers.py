"""Timer / failure-detection tests driven by FakeTimerProvider.

Mirrors the reference's timer tests (core/internal/clientstate/
timeout_test.go:46-80 against a mock timer provider) and the timeout
behaviors of core/timeout.go:45-72 (request timeout → signed
REQ-VIEW-CHANGE, deduplicated via expectedView) and core/request.go:315-324
(prepare timeout → forward the starved request to the primary's unicast
log).  No real time elapses: timers are fired explicitly.
"""

import asyncio

from minbft_tpu import api
from minbft_tpu.core import new_replica
from minbft_tpu.core.internal.timer import FakeTimerProvider
from minbft_tpu.messages import ReqViewChange, Request, authen_bytes
from minbft_tpu.sample.authentication import new_test_authenticators
from minbft_tpu.sample.config import SimpleConfiger
from minbft_tpu.sample.conn.inprocess import (
    InProcessPeerConnector,
    make_testnet_stubs,
)
from minbft_tpu.sample.requestconsumer import SimpleLedger


def _make_backup(n=3, f=1, replica_id=1):
    """A single backup replica (view 0 primary is replica 0) with fake
    timers and no network started — we poke handlers directly."""
    timers = FakeTimerProvider()
    configer = SimpleConfiger(n=n, f=f, timeout_request=5.0, timeout_prepare=2.0)
    replica_auths, client_auths = new_test_authenticators(
        n, n_clients=1, usig_kind="hmac", engine=None
    )
    stubs = make_testnet_stubs(n)
    r = new_replica(
        replica_id,
        configer,
        replica_auths[replica_id],
        InProcessPeerConnector(stubs),
        SimpleLedger(),
        timer_provider=timers,
    )
    return r, timers, replica_auths, client_auths


def _signed_request(client_auth, seq=1, op=b"op"):
    req = Request(client_id=0, seq=seq, operation=op)
    req.signature = client_auth.generate_message_authen_tag(
        api.AuthenticationRole.CLIENT, authen_bytes(req)
    )
    return req


def test_request_timeout_emits_signed_req_view_change_once():
    """Request timer expiry demands view v+1 exactly once: a signed
    REQ-VIEW-CHANGE hits the broadcast log, and a second expiry for the
    same view is deduplicated via expectedView (reference
    core/timeout.go:45-72)."""

    async def run():
        r, timers, replica_auths, client_auths = _make_backup()
        h = r.handlers
        req = _signed_request(client_auths[0])
        await h.handle_peer_message(req)  # backup accepts a forwarded request

        assert len(timers.timers) >= 1  # request + prepare timers armed
        timers.fire_all()
        # Timer callbacks schedule a task; let it run.
        await asyncio.sleep(0)
        await asyncio.sleep(0)

        log = list(h.message_log.snapshot())
        rvcs = [m for m in log if isinstance(m, ReqViewChange)]
        assert len(rvcs) == 1
        rvc = rvcs[0]
        assert rvc.new_view == 1
        assert rvc.replica_id == r.id
        # The emitted message is properly signed (replica role).
        await replica_auths[0].verify_message_authen_tag(
            api.AuthenticationRole.REPLICA,
            r.id,
            authen_bytes(rvc),
            rvc.signature,
        )

        # A second expiry for the same view is a no-op (dedup).
        await h.handle_request_timeout(0)
        await asyncio.sleep(0)
        rvcs = [m for m in h.message_log.snapshot() if isinstance(m, ReqViewChange)]
        assert len(rvcs) == 1

    asyncio.run(run())


def test_prepare_timeout_forwards_request_to_primary():
    """A backup whose request is never prepared forwards it to the primary's
    unicast log on prepare-timer expiry (reference core/request.go:315-324)."""

    async def run():
        r, timers, _, client_auths = _make_backup()
        h = r.handlers
        req = _signed_request(client_auths[0], seq=7)
        await h.handle_peer_message(req)

        primary_log_before = list(h.unicast_logs[0].snapshot())
        assert req not in primary_log_before

        timers.fire_all()
        await asyncio.sleep(0)

        forwarded = list(h.unicast_logs[0].snapshot())
        assert any(
            isinstance(m, Request) and m.seq == 7 and m.client_id == 0
            for m in forwarded
        )

    asyncio.run(run())


def test_a_stopped_replica_leaves_no_timer_armed():
    """stop() cancels the request and prepare timers the replica armed, and
    arms none for a message that is still on its way through: a replica
    that was taken down counts no timeout after it went, forwards nothing
    and demands no view."""

    async def run():
        r, timers, _, client_auths = _make_backup()
        h = r.handlers
        await h.handle_peer_message(_signed_request(client_auths[0]))
        assert sum(not t.cancelled for t in timers.timers) == 2  # request + prepare
        counted = dict(h.metrics.counters)
        await r.stop()
        assert all(t.cancelled for t in timers.timers)
        timers.fire_all()
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert h.metrics.counters.get("timeouts_request", 0) == counted.get("timeouts_request", 0)
        assert h.metrics.counters.get("timeouts_prepare", 0) == counted.get("timeouts_prepare", 0)
        assert not any(isinstance(m, ReqViewChange) for m in h.message_log.snapshot())
        assert not list(h.unicast_logs[0].snapshot())
        # a request whose validation ends after the stop arms nothing either
        await h.handle_peer_message(_signed_request(client_auths[0], seq=2))
        assert all(t.cancelled for t in timers.timers)

    asyncio.run(run())


def test_timers_stop_on_commit():
    """Committing a request cancels its client's request+prepare timers: a
    later fire_all must not emit a view-change demand."""

    async def run():
        n, f = 3, 1
        timers_by_replica = [FakeTimerProvider() for _ in range(n)]
        configer = SimpleConfiger(
            n=n, f=f, timeout_request=5.0, timeout_prepare=2.0
        )
        replica_auths, client_auths = new_test_authenticators(
            n, n_clients=1, usig_kind="hmac", engine=None
        )
        stubs = make_testnet_stubs(n)
        ledgers = [SimpleLedger() for _ in range(n)]
        replicas = []
        for i in range(n):
            r = new_replica(
                i,
                configer,
                replica_auths[i],
                InProcessPeerConnector(stubs),
                ledgers[i],
                timer_provider=timers_by_replica[i],
            )
            stubs[i].assign_replica(r)
            replicas.append(r)
        for r in replicas:
            await r.start()

        from minbft_tpu.client import new_client
        from minbft_tpu.sample.conn.inprocess import InProcessClientConnector

        client = new_client(
            0, n, f, client_auths[0], InProcessClientConnector(stubs), seq_start=0
        )
        await client.start()
        await asyncio.wait_for(client.request(b"x"), 30)

        # Let commit propagation finish on all replicas.
        for _ in range(100):
            if all(lg.length >= 1 for lg in ledgers):
                break
            await asyncio.sleep(0.01)

        for i, (r, timers) in enumerate(zip(replicas, timers_by_replica)):
            timers.fire_all()
        await asyncio.sleep(0.05)

        for r in replicas:
            rvcs = [
                m
                for m in r.handlers.message_log.snapshot()
                if isinstance(m, ReqViewChange)
            ]
            assert not rvcs, f"replica {r.id} demanded a view change after commit"

        await client.stop()
        for r in replicas:
            await r.stop()

    asyncio.run(run())
