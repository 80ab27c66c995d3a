"""Multi-group consensus sharding (minbft_tpu/groups): codec envelope,
shard router, GroupRuntime demux, cross-group engine coalescing, group
isolation, and the G=4 seeded chaos soak.

Seed discipline matches tests/test_chaos.py: MINBFT_CHAOS_SEED replays a
failure byte-identically; the soak's committed default seed is pinned in
CI (the multi-group step runs this file WITHOUT the `not slow` filter).
"""

import asyncio
import json
import logging
import os
import sys

import pytest

from conftest import ledgers_reach
from minbft_tpu import api
from minbft_tpu.groups import (
    GroupAuthenticator,
    GroupRuntime,
    MultiGroupClient,
    ShardRouter,
    group_for_key,
)
from minbft_tpu.messages import (
    CodecError,
    marshal,
    pack_group,
    split_group,
    split_group_batch,
    Request,
)
from minbft_tpu.sample.authentication import new_test_authenticators
from minbft_tpu.sample.config import SimpleConfiger
from minbft_tpu.sample.conn.inprocess import (
    InProcessClientConnector,
    InProcessPeerConnector,
    make_testnet_stubs,
)
from minbft_tpu.sample.requestconsumer import SimpleLedger
from minbft_tpu.testing import FaultNet, FaultPlan, InvariantChecker, chaos_seed

# Dev-mode wall-clock stretch, exactly tests/test_chaos.py's contract:
# the seeded fault schedule is frame-indexed, so scaling every timeout
# keeps replay byte-identical while the asyncio-debug-slowed cluster
# gets proportionate patience.
TIME_SCALE = 5.0 if sys.flags.dev_mode else 1.0


def _t(seconds: float) -> float:
    return seconds * TIME_SCALE


def _group_ledgers(ledgers, g):
    """Every replica's ledger of group ``g``."""
    return [row[g] for row in ledgers]


_log = logging.getLogger("minbft.groups.test")


# ---------------------------------------------------------------------------
# codec: the group envelope.


def test_group_envelope_roundtrip():
    frame = marshal(Request(client_id=3, seq=9, operation=b"op"))
    for gid in (1, 7, 0xFFFF):
        wrapped = pack_group(gid, frame)
        assert wrapped != frame
        assert split_group(wrapped) == (gid, frame)
    # group 0 is BARE by definition: one canonical encoding per frame.
    assert pack_group(0, frame) == frame
    assert split_group(frame) == (0, frame)
    with pytest.raises(CodecError):
        pack_group(0x10000, frame)
    with pytest.raises(CodecError):
        split_group(bytes([0xF1, 0x00]))  # truncated envelope


def test_split_group_batch_matches_scalar():
    # Above the vectorized threshold (48): mixed bare/tagged/malformed
    # frames must classify identically to the scalar path, item-wise.
    frames = []
    expect = []
    for i in range(120):
        inner = marshal(Request(client_id=i, seq=i, operation=b"x" * (i % 7)))
        gid = i % 5
        frames.append(pack_group(gid, inner))
        expect.append((gid, inner))
    # malformed: truncated envelope (tag present, id cut off)
    frames.append(bytes([0xF1, 0x01]))
    expect.append(None)  # CodecError slot
    frames.append(b"")  # empty frame is bare group 0
    expect.append((0, b""))
    out = split_group_batch(frames)
    assert len(out) == len(frames)
    for got, want in zip(out, expect):
        if want is None:
            assert isinstance(got[0], CodecError)
        else:
            assert got == want
    # and the scalar path (below the threshold) agrees
    small = frames[:10] + frames[-2:]
    small_expect = expect[:10] + expect[-2:]
    for got, want in zip(split_group_batch(small), small_expect):
        if want is None:
            assert isinstance(got[0], CodecError)
        else:
            assert got == want


# ---------------------------------------------------------------------------
# shard router: same key -> same group, across restarts and processes.


def test_shard_router_is_deterministic_across_restarts():
    # group_for_key is a pure function of (key, G) — SHA-256, no state,
    # no seed.  Pin exact values so an accidental hash change (which
    # would silently re-shard every deployed key space) fails loudly.
    assert group_for_key(b"", 4) == group_for_key(b"", 4)
    vals = {k: group_for_key(k, 8) for k in (b"a", b"b", b"user:42", b"\x00")}
    # recompute "after a restart" (fresh router objects)
    for k, v in vals.items():
        assert ShardRouter(8).group_for(k) == v
    # the committed pins (sha256 first-8-bytes big-endian mod G):
    assert group_for_key(b"user:42", 8) == 2
    assert group_for_key(b"a", 8) == 2
    assert group_for_key(b"", 4) == 0
    # G=1 shortcut and input validation
    assert group_for_key(b"anything", 1) == 0
    with pytest.raises(ValueError):
        group_for_key(b"x", 0)
    # rough uniformity: 256 keys over 4 groups, no group starved
    counts = [0] * 4
    for i in range(256):
        counts[group_for_key(b"key-%d" % i, 4)] += 1
    assert min(counts) > 256 // 4 // 3, counts


def test_group_authenticator_domain_separation():
    async def run():
        (r_auths, _c), = [new_test_authenticators(1, n_clients=1)]
        base = r_auths[0]
        g1 = GroupAuthenticator(base, 1)
        g2 = GroupAuthenticator(base, 2)
        g0 = GroupAuthenticator(base, 0)
        msg = b"payload"
        tag = g1.generate_message_authen_tag(
            api.AuthenticationRole.REPLICA, msg
        )
        await g1.verify_message_authen_tag(
            api.AuthenticationRole.REPLICA, 0, msg, tag
        )
        # the same bytes+tag must NOT verify in another group
        with pytest.raises(api.AuthenticationError):
            await g2.verify_message_authen_tag(
                api.AuthenticationRole.REPLICA, 0, msg, tag
            )
        # group 0 is the empty prefix: byte-compatible with the base
        tag0 = g0.generate_message_authen_tag(
            api.AuthenticationRole.REPLICA, msg
        )
        await base.verify_message_authen_tag(
            api.AuthenticationRole.REPLICA, 0, msg, tag0
        )
        # batch surface applies the same prefix item-wise
        out = await g2.verify_message_authen_tags(
            api.AuthenticationRole.REPLICA, [(0, msg, tag), (0, msg, tag0)]
        )
        assert all(isinstance(e, api.AuthenticationError) for e in out)
        return True

    assert asyncio.run(run())


# ---------------------------------------------------------------------------
# cluster helper.


async def make_group_cluster(
    n=4,
    f=1,
    n_groups=2,
    n_clients=2,
    cfg=None,
    usig_kind="hmac",
    wrap_group_connector=None,
    **auth_kw,
):
    """In-process G-group cluster over the real shared-channel mux.
    Returns (runtimes, per_group_client_auths, stubs, ledgers) with
    ledgers[i][g] = replica i's group-g ledger."""
    if cfg is None:
        cfg = SimpleConfiger(
            n=n, f=f, timeout_request=60.0, timeout_prepare=30.0
        )
    per_group = [
        new_test_authenticators(
            n, n_clients=n_clients, usig_kind=usig_kind, **auth_kw
        )
        for _ in range(n_groups)
    ]
    stubs = make_testnet_stubs(n)
    ledgers = [
        [SimpleLedger() for _ in range(n_groups)] for _ in range(n)
    ]
    runtimes = []
    for i in range(n):
        rt = GroupRuntime(
            i,
            cfg,
            [per_group[g][0][i] for g in range(n_groups)],
            InProcessPeerConnector(stubs),
            ledgers[i],
            wrap_group_connector=(
                (lambda g, c, _i=i: wrap_group_connector(g, c, _i))
                if wrap_group_connector is not None
                else None
            ),
        )
        stubs[i].assign_replica(rt)
        runtimes.append(rt)
    for rt in runtimes:
        await rt.start()
    client_auths = [per_group[g][1] for g in range(n_groups)]
    return runtimes, client_auths, stubs, ledgers


def _mg_client(client_id, n, f, client_auths, stubs, **kw):
    return MultiGroupClient(
        client_id,
        n,
        f,
        len(client_auths),
        [client_auths[g][client_id] for g in range(len(client_auths))],
        InProcessClientConnector(stubs),
        retransmit_interval=kw.pop("retransmit_interval", 30.0),
        **kw,
    )


# ---------------------------------------------------------------------------
# runtime: commit across groups on shared transport.


def test_group_runtime_commits_across_groups():
    async def run():
        runtimes, c_auths, stubs, ledgers = await make_group_cluster(
            n=4, f=1, n_groups=2
        )
        client = _mg_client(0, 4, 1, c_auths, stubs)
        await client.start()
        try:
            ops = [b"op-%d" % k for k in range(8)]
            results = await asyncio.wait_for(
                asyncio.gather(*[client.request(op) for op in ops]), _t(60)
            )
            assert all(results)
            per_g = [0, 0]
            for op in ops:
                per_g[client.group_for(op)] += 1
            assert all(per_g), f"hash routing starved a group: {per_g}"
            # every replica's per-group ledger holds exactly its shard
            for g in range(2):
                await ledgers_reach(_group_ledgers(ledgers, g), per_g[g], _t(5))
                lens = [ledgers[i][g].length for i in range(4)]
                assert all(l == per_g[g] for l in lens), (g, lens, per_g)
            # per-group observability labels are threaded through
            for rt in runtimes:
                assert [c.group for c in rt.cores] == [0, 1]
                assert [c.metrics.group for c in rt.cores] == [0, 1]
            agg = runtimes[0].metrics_aggregate()
            assert agg.get("requests_executed", 0) == len(ops)
        finally:
            await client.stop()
            for rt in runtimes:
                await rt.stop()
        return True

    assert asyncio.run(run())


def test_pinned_group_and_unknown_group_frames():
    async def run():
        runtimes, c_auths, stubs, ledgers = await make_group_cluster(
            n=4, f=1, n_groups=2
        )
        client = _mg_client(0, 4, 1, c_auths, stubs)
        await client.start()
        try:
            # explicit pinning beats the hash route
            await asyncio.wait_for(
                client.request(b"pinned", group=1), _t(60)
            )
            await ledgers_reach(_group_ledgers(ledgers, 1), 1, _t(5))
            assert [ledgers[i][1].length for i in range(4)] == [1] * 4
            assert all(ledgers[i][0].length == 0 for i in range(4))
            with pytest.raises(ValueError):
                await client.request(b"x", group=7)
            # frames for an unknown group are dropped, never detonate:
            # inject one straight into replica 0's client stream.
            handler = runtimes[0].client_message_stream_handler()

            async def one_shot():
                yield pack_group(
                    9, marshal(Request(client_id=0, seq=1, operation=b"z"))
                )

            out = handler.handle_message_stream(one_shot())
            with pytest.raises((asyncio.TimeoutError, StopAsyncIteration)):
                # no reply ever comes back for an unknown group — the
                # stream just drains and ends (or stays silent)
                await asyncio.wait_for(out.__anext__(), _t(0.6))
            await out.aclose()
            # and the cluster still works afterwards
            await asyncio.wait_for(client.request(b"after", group=0), _t(60))
            await ledgers_reach(_group_ledgers(ledgers, 0), 1, _t(5))
            assert [ledgers[i][0].length for i in range(4)] == [1] * 4
        finally:
            await client.stop()
            for rt in runtimes:
                await rt.stop()
        return True

    assert asyncio.run(run())


# ---------------------------------------------------------------------------
# cross-group engine coalescing: the tentpole's measurable claim.


def _spy_host_sig_queue(engine):
    """Wrap the host ECDSA verify queue's dispatcher to record every
    dispatched batch (host queue: items are exactly the submitted
    (pub, digest, sig) lanes — no padding)."""
    q = engine._queue("ecdsa_p256_host", engine._dispatch_ecdsa_host)
    batches = []
    orig = q.dispatch

    def spy(items):
        batches.append(list(items))
        return orig(items)

    q.dispatch = spy
    return q, batches


async def _run_coalescing_cluster(n_groups, per_group_requests, clients=2):
    """Fixed per-group load through one shared engine; returns
    (recorded host-sig-queue batches, pub->group map, queue stats)."""
    from minbft_tpu.parallel import BatchVerifier

    engine = BatchVerifier(max_batch=64, buckets=(64,))
    # Keep the USIG off the device path on the CPU test backend: route
    # its MAC checks through the engine's host HMAC queue (same
    # coalescing semantics, no kernel compile).
    engine.verify_hmac_sha256 = engine.verify_hmac_sha256_host
    q, batches = _spy_host_sig_queue(engine)
    runtimes, c_auths, stubs, ledgers = await make_group_cluster(
        n=4,
        f=1,
        n_groups=n_groups,
        n_clients=clients,
        engine=engine,
        batch_signatures=False,  # client/replica sigs -> engine HOST queue
    )
    pub_to_group = {}
    for g in range(n_groups):
        for pub in c_auths[g][0]._client_pubs.values():
            pub_to_group[pub] = g
    mclients = [
        _mg_client(c, 4, 1, c_auths, stubs) for c in range(clients)
    ]
    for mc in mclients:
        await mc.start()
    try:
        # identical per-group wave structure at every G: each wave fires
        # one request per (client, group) concurrently.
        for wave in range(per_group_requests):
            await asyncio.wait_for(
                asyncio.gather(
                    *[
                        mc.request(b"w-%d-%d" % (mc.client_id, wave), group=g)
                        for mc in mclients
                        for g in range(n_groups)
                    ]
                ),
                _t(60),
            )
    finally:
        for mc in mclients:
            await mc.stop()
        for rt in runtimes:
            await rt.stop()
    return batches, pub_to_group, q.stats


@pytest.mark.slow
def test_one_engine_flush_spans_groups():
    """THE coalescing differential: with G=2 on one engine, at least one
    dispatched verify batch must contain client-signature lanes from BOTH
    groups (the grouped ingest seeds every group's checks in the same
    loop turn, ahead of one flush decision)."""
    batches, pub_to_group, _stats = asyncio.run(
        _run_coalescing_cluster(n_groups=2, per_group_requests=6)
    )
    assert batches, "no host-sig batches dispatched"
    spans = [
        {pub_to_group[pub] for pub, _d, _s in b if pub in pub_to_group}
        for b in batches
    ]
    assert any(len(s) >= 2 for s in spans), (
        f"no flush spanned groups: {[sorted(s) for s in spans]}"
    )


@pytest.mark.slow
def test_verify_mean_batch_rises_with_groups():
    """At FIXED per-group load, the shared queue's mean batch fill must
    rise with G — the 'device sees one big batch regardless of group
    count' claim, as a differential."""
    _b1, _m1, stats1 = asyncio.run(
        _run_coalescing_cluster(n_groups=1, per_group_requests=8)
    )
    _b2, _m2, stats2 = asyncio.run(
        _run_coalescing_cluster(n_groups=2, per_group_requests=8)
    )
    m1 = stats1.mean_batch
    m2 = stats2.mean_batch
    assert stats1.items and stats2.items
    # G=2 delivers ~2x the lanes into the same flush windows; demand a
    # clear rise with margin for scheduling noise.
    assert m2 >= m1 * 1.2, (m1, m2)


# ---------------------------------------------------------------------------
# group isolation: a wedged group never blocks another group's commits.


def test_wedged_group_does_not_block_others():
    async def run():
        # Black-hole EVERY peer link of group 1 (drop=1.0 via a
        # group-scoped faultnet between its cores and the shared mux);
        # group 0 shares the same physical channels and must keep
        # committing.  Long protocol timeouts: the wedged group parks,
        # it doesn't view-change-thrash.
        net = FaultNet(seed=0xB10C, default_plan=FaultPlan(drop=1.0))
        runtimes, c_auths, stubs, ledgers = await make_group_cluster(
            n=4,
            f=1,
            n_groups=2,
            wrap_group_connector=(
                lambda g, c, i: net.wrap(c, f"r{i}") if g == 1 else c
            ),
        )
        client = _mg_client(0, 4, 1, c_auths, stubs)
        await client.start()
        try:
            # the wedged group cannot commit (sanity: the wedge is real)
            with pytest.raises(asyncio.TimeoutError):
                await asyncio.wait_for(
                    client.request(b"wedged", group=1), _t(2.0)
                )
            # ...while the healthy group commits a full batch
            ops = [b"ok-%d" % k for k in range(6)]
            await asyncio.wait_for(
                asyncio.gather(
                    *[client.request(op, group=0) for op in ops]
                ),
                _t(60),
            )
            await ledgers_reach(_group_ledgers(ledgers, 0), len(ops), _t(5))
        finally:
            await client.stop()
            for rt in runtimes:
                await rt.stop()
        return True

    assert asyncio.run(run())


def test_saturated_group_processor_never_blocks_the_shared_drain(monkeypatch):
    """HOL differential at the HANDLER layer (the transport layer's
    drop-on-full is covered above): shrink the per-group processor bound,
    park more than that many requests in a wedged group, and require the
    healthy group to commit THROUGH the same shared stream.  Pre-fix the
    shared tick loop blocked in the wedged group's submit and this times
    out; post-fix the wedged group sheds (client retransmission heals)
    and the drain keeps moving."""
    from minbft_tpu.core import message_handling as mh

    monkeypatch.setattr(mh, "_STREAM_CONCURRENCY", 4)

    async def run():
        net = FaultNet(seed=0xB10C2, default_plan=FaultPlan(drop=1.0))
        runtimes, c_auths, stubs, ledgers = await make_group_cluster(
            n=4,
            f=1,
            n_groups=2,
            wrap_group_connector=(
                lambda g, c, i: net.wrap(c, f"r{i}") if g == 1 else c
            ),
        )
        client = _mg_client(0, 4, 1, c_auths, stubs)
        await client.start()
        floods = []
        try:
            # 3x the patched bound into the black-holed group: its
            # handlers park awaiting a quorum that can never form, so
            # the processor saturates and starts shedding.
            floods = [
                asyncio.ensure_future(
                    client.request(b"flood-%d" % k, group=1)
                )
                for k in range(12)
            ]
            await asyncio.sleep(_t(1.0))  # reach the replicas and park
            await asyncio.wait_for(client.request(b"ok", group=0), _t(60))
            await ledgers_reach(_group_ledgers(ledgers, 0), 1, _t(5))
        finally:
            for t in floods:
                t.cancel()
            await asyncio.gather(*floods, return_exceptions=True)
            await client.stop()
            for rt in runtimes:
                await rt.stop()
        return True

    assert asyncio.run(run())


# ---------------------------------------------------------------------------
# observability plumbing: labels, dumps, exposition.


def test_group_labels_in_trace_and_prom():
    from minbft_tpu.obs.prom import (
        collect_replica,
        merge_family_lists,
        render_families,
    )
    from minbft_tpu.obs.trace import FlightRecorder, dump_path_for, filter_group
    from minbft_tpu.utils.metrics import ReplicaMetrics

    rec = FlightRecorder.for_replica(2, group=3)
    assert rec.to_dict()["group"] == 3
    assert dump_path_for("replica", 2, base="/tmp/x", group=3) == (
        "/tmp/x.r2g3.json"
    )
    assert dump_path_for("replica", 2, base="/tmp/x") == "/tmp/x.r2.json"
    docs = [
        {"kind": "replica", "group": 0, "hists": {}},
        {"kind": "replica", "group": 1, "hists": {}},
        {"kind": "engine", "hists": {}},  # shared: survives any filter
    ]
    kept = filter_group(docs, 1)
    assert {d.get("group") for d in kept} == {1, None}
    m = ReplicaMetrics(group=2)
    m.inc("requests_executed", 5)
    text = render_families(
        merge_family_lists(
            [
                collect_replica(metrics=m, replica_id=0),
                collect_replica(
                    metrics=ReplicaMetrics(group=3), replica_id=0
                ),
            ]
        )
    )
    assert 'group="2"' in text
    # one family block even with two groups' samples
    assert text.count("# TYPE minbft_uptime_seconds gauge") == 1


# ---------------------------------------------------------------------------
# THE multi-group chaos soak (satellite): G=4 on shared transport,
# partition/heal + primary stall in ONE group only; per-group invariants
# hold, untouched groups keep committing, census replays from the seed.

GROUPS_CHAOS_PLAN = FaultPlan(
    drop=0.03,
    delay=0.08,
    delay_s=(0.0005, 0.005),
    duplicate=0.03,
    reorder=0.05,
    corrupt=0.02,
)

_CHAOS_GROUP = 2  # the group that takes the faults


@pytest.mark.slow
def test_groups_chaos_soak_one_group_faulted():
    seed = chaos_seed(default=0x64A05)
    G = 4

    async def run():
        net = FaultNet(seed=seed, default_plan=GROUPS_CHAOS_PLAN)
        # Patience scaled to the G=4 single-event-loop operating point:
        # four groups' pure-Python crypto share one loop, so loop
        # latency under load is ~4x the ungrouped soak's — sub-second
        # request timers would fire spuriously and spiral the chaos
        # group into view-change thrash whose (pure-Python-verified)
        # whole-log VIEW-CHANGE storms then starve every group.
        cfg = SimpleConfiger(
            n=4,
            f=1,
            timeout_request=_t(2.5),
            timeout_prepare=_t(1.2),
            timeout_viewchange=_t(2.5),
        )
        runtimes, c_auths, stubs, ledgers = await make_group_cluster(
            n=4,
            f=1,
            n_groups=G,
            cfg=cfg,
            wrap_group_connector=(
                lambda g, c, i: (
                    net.wrap(c, f"r{i}") if g == _CHAOS_GROUP else c
                )
            ),
        )
        client = _mg_client(0, 4, 1, c_auths, stubs,
                            retransmit_interval=_t(1.0), max_inflight=8)
        await client.start()
        accepted = {g: [] for g in range(G)}

        async def issue(g, tag, k, timeout=90):
            ops = [b"g%d-%s-%d" % (g, tag, i) for i in range(k)]
            results = await asyncio.gather(
                *[
                    client.request(op, group=g, timeout=_t(timeout))
                    for op in ops
                ]
            )
            accepted[g].extend(zip(ops, results))

        untouched = [g for g in range(G) if g != _CHAOS_GROUP]
        try:
            # Phase A: seeded chaos on the target group, traffic to ALL.
            _log.warning("groups chaos A: 2 req/group under seeded plan")
            await issue(_CHAOS_GROUP, b"a", 2)
            await asyncio.gather(*[issue(g, b"a", 2) for g in untouched])

            # Phase B: partition the TARGET group {r0,r1}|{r2,r3} (its
            # links only — the same physical channels keep carrying the
            # other groups).  Target requests resolve after heal;
            # untouched groups must commit DURING the partition.
            _log.warning("groups chaos B: partition group %d", _CHAOS_GROUP)
            net.partition({"r0", "r1"}, {"r2", "r3"})
            target_b = asyncio.ensure_future(issue(_CHAOS_GROUP, b"b", 3))
            # untouched groups must commit DURING the partition — the
            # isolation claim under live faults (with n=4/f=1 the
            # partitioned group itself may or may not commit, depending
            # on which side holds its current primary: f+1=2 commits
            # suffice, so no assertion either way until after heal).
            await asyncio.gather(*[issue(g, b"b", 2) for g in untouched])
            await asyncio.sleep(_t(0.5))
            net.heal_partition()
            _log.warning("groups chaos B: healed")
            await target_b

            # settle the target group's view before stalling its primary
            deadline = asyncio.get_running_loop().time() + _t(30)
            view = 0
            while asyncio.get_running_loop().time() < deadline:
                views = []
                for rt in runtimes:
                    cur, _ = await rt.group(
                        _CHAOS_GROUP
                    ).handlers.view_state.hold_view()
                    views.append(cur)
                if len(set(views)) == 1:
                    view = views[0]
                    break
                await asyncio.sleep(0.1)

            # Phase C: stall the target group's CURRENT primary (its
            # links only — the same replica's cores in other groups keep
            # running undisturbed).  The target group must depose it;
            # untouched groups commit throughout.
            primary = view % 4
            _log.warning(
                "groups chaos C: stalling group-%d primary r%d (view %d)",
                _CHAOS_GROUP, primary, view,
            )
            net.stall_replica(primary)
            target_c = asyncio.ensure_future(issue(_CHAOS_GROUP, b"c", 3))
            await asyncio.gather(*[issue(g, b"c", 2) for g in untouched])
            await target_c
            survivors = [rt for rt in runtimes if rt.id != primary]
            views = {}
            deadline = asyncio.get_running_loop().time() + _t(30)
            while asyncio.get_running_loop().time() < deadline:
                for rt in survivors:
                    cur, _ = await rt.group(
                        _CHAOS_GROUP
                    ).handlers.view_state.hold_view()
                    views[rt.id] = cur
                if all(v > view for v in views.values()):
                    break
                await asyncio.sleep(0.05)
            assert all(v > view for v in views.values()), (
                f"group-{_CHAOS_GROUP} survivors still at {views}"
            )
            # the UNTOUCHED groups never left view 0 (their primary —
            # the same OS-level replica — was never stalled for them)
            for g in untouched:
                for rt in runtimes:
                    cur, _ = await rt.group(g).handlers.view_state.hold_view()
                    assert cur == 0, (g, rt.id, cur)
            net.unstall_replica(primary)

            # freeze the seeded census before heal clears the plan
            frames_snapshot = dict(net.census.frames)
            live_seeded = dict(net.census.seeded_counts())

            # Phase D: heal + reset, clean tail on every group.
            _log.warning("groups chaos D: heal + tail")
            net.heal()
            net.reset_all()
            await asyncio.gather(*[issue(g, b"d", 1, 60) for g in range(G)])

            # every group's accepted set committed on every replica
            per_group_expected = {
                g: len(accepted[g]) for g in range(G)
            }
            assert per_group_expected[_CHAOS_GROUP] == 9
            for g in range(G):
                await ledgers_reach(
                    _group_ledgers(ledgers, g), per_group_expected[g], _t(60)
                )

            # per-group safety invariants over per-group cores/ledgers
            summaries = {}
            for g in range(G):
                checker = InvariantChecker(
                    [rt.group(g) for rt in runtimes],
                    _group_ledgers(ledgers, g),
                )
                summaries[g] = checker.check(accepted[g])
            # the injected faults really happened, in the target group's
            # world only, and replay the seed exactly
            assert net.census.counters.get("partition", 0) >= 1
            assert net.census.counters.get("stall", 0) >= 1
            replayed = net.replay_counts(
                frames_snapshot, plan=GROUPS_CHAOS_PLAN
            )
            assert replayed == live_seeded, (replayed, live_seeded)
            out = net.census.snapshot()
            out["seed"] = seed
            out["groups"] = G
            out["chaos_group"] = _CHAOS_GROUP
            out["time_scale"] = TIME_SCALE
            out["requests_committed"] = {
                str(g): per_group_expected[g] for g in range(G)
            }
            out["invariants"] = {str(g): summaries[g] for g in range(G)}
            return out
        finally:
            await client.stop()
            for rt in runtimes:
                await rt.stop()

    try:
        census = asyncio.run(run())
    except BaseException:
        print(f"replay with MINBFT_CHAOS_SEED={seed}")
        raise
    assert census["frames_total"] > 0
    census_path = os.environ.get("MINBFT_GROUPS_CHAOS_CENSUS")
    if census_path:
        with open(census_path, "w") as fh:
            json.dump(census, fh, indent=2, sort_keys=True)
            fh.write("\n")


# ---------------------------------------------------------------------------
# CLI + config plumbing: declare G once, every layer sees it.


def test_testnet_scaffold_declares_groups_and_config_layers(tmp_path, monkeypatch):
    from minbft_tpu.sample.config import load_config
    from minbft_tpu.sample.peer.cli import main

    d = str(tmp_path)
    rc = main(
        ["testnet", "-n", "4", "--clients", "1", "-d", d,
         "--usig", "HMAC_SHA256", "--base-port", "45300", "--groups", "8"]
    )
    assert rc == 0
    cfg = load_config(f"{d}/consensus.yaml")
    assert cfg.groups == 8
    # env layering (CONSENSUS_GROUPS, the test/bench override path)
    cfg2 = load_config(f"{d}/consensus.yaml", env={"CONSENSUS_GROUPS": "2"})
    assert cfg2.groups == 2
    # an ungrouped scaffold stays at the ungrouped default
    rc = main(
        ["testnet", "-n", "4", "--clients", "1", "-d", f"{d}/plain",
         "--usig", "HMAC_SHA256", "--base-port", "45310"]
    )
    assert rc == 0
    assert load_config(f"{d}/plain/consensus.yaml").groups == 1
