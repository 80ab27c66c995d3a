"""View-change protocol tests (beyond the reference, which stops at the
REQ-VIEW-CHANGE demand): unit tests for the re-proposal-set derivation and
the USIG log-completeness validation, plus the money test — an in-process
cluster survives a crashed primary and keeps committing requests through
the new view."""

import asyncio
import contextvars
import time

import pytest

from conftest import make_cluster
from minbft_tpu import api
from minbft_tpu.core import viewchange as vc_mod
from minbft_tpu.messages import (
    UI,
    Commit,
    NewView,
    Prepare,
    Request,
    ViewChange,
    marshal,
    unmarshal,
)


def _req(client_id=1, seq=1):
    return Request(client_id=client_id, seq=seq, operation=b"op")


def _prepare(cv, view=0, primary=0, reqs=None):
    return Prepare(
        replica_id=primary,
        view=view,
        requests=reqs or [_req(seq=cv)],
        ui=UI(counter=cv, cert=b"c"),
    )


def test_compute_new_view_set_orders_and_dedups():
    p1 = _prepare(1)
    p2 = _prepare(2)
    c1 = Commit(replica_id=1, prepare=p1, ui=UI(counter=1, cert=b"d"))
    c2 = Commit(replica_id=2, prepare=p2, ui=UI(counter=1, cert=b"e"))
    # replica 1 saw both prepares (p1 via its commit, p2 directly is not
    # possible for a backup — use commits); replica 2 saw only p2
    vc1 = ViewChange(replica_id=1, new_view=1, log=(c1,), ui=UI(counter=2))
    vc2 = ViewChange(replica_id=2, new_view=1, log=(c2,), ui=UI(counter=2))
    s = vc_mod.compute_new_view_set([vc1, vc2, vc1], 1)
    assert [p.ui.counter for p in s] == [1, 2]
    # prepares of the new view itself (or later) are excluded
    p_new = _prepare(5, view=1, primary=1)
    vc3 = ViewChange(
        replica_id=3, new_view=1, log=(p_new,), ui=UI(counter=1)
    )
    assert vc_mod.compute_new_view_set([vc3], 1) == []


def test_compute_new_view_set_collapses_reproposed_batches():
    """A batch surviving several failed transitions appears in the quorum
    logs once per view it was (re-)proposed in — under different UIs, so
    slot dedup alone keeps them all and S doubles per failed view change
    (the chaos soak livelocked at 768 re-proposals of 6 requests).  The
    batch must be kept ONCE, at its LATEST (view, counter) slot, with
    genuinely distinct batches still ordered around it."""
    orig_a = _prepare(5, view=0, primary=0, reqs=[_req(1, 1)])
    orig_b = _prepare(6, view=0, primary=0, reqs=[_req(1, 2)])
    # view 1's primary re-proposed both (new UIs, same batches), then a
    # fresh batch c was proposed after the re-proposals
    re_a = _prepare(3, view=1, primary=1, reqs=[_req(1, 1)])
    re_b = _prepare(4, view=1, primary=1, reqs=[_req(1, 2)])
    fresh_c = _prepare(5, view=1, primary=1, reqs=[_req(1, 3)])
    vc1 = ViewChange(
        replica_id=1, new_view=2, log=(orig_a, orig_b), ui=UI(counter=9)
    )
    vc2 = ViewChange(
        replica_id=2, new_view=2, log=(re_a, re_b, fresh_c), ui=UI(counter=9)
    )
    s = vc_mod.compute_new_view_set([vc1, vc2], 2)
    assert [(p.view, p.ui.counter) for p in s] == [(1, 3), (1, 4), (1, 5)]
    assert [vc_mod.batch_key(p) for p in s] == [
        ((1, 1),), ((1, 2),), ((1, 3),)
    ]


def test_compute_new_view_set_ignores_stale_primary_slots():
    """The chaos-soak ledger fork (ISSUE 5): a deposed primary stalled
    through its own view change keeps certifying fresh PREPAREs for
    client retransmissions at its OLD view.  Those slots exist only in
    its own log and sort before every later view — an earliest-slot
    dedup would order the late batch BEFORE batches the live quorum
    committed first, forking the healed replica's ledger.  Latest-slot
    dedup must order by the genuine (newest-view) slots instead."""
    # Live history: batch X committed at view 1 slot 3, then batch Y
    # proposed at view 1 slot 4.
    live_x = _prepare(3, view=1, primary=1, reqs=[_req(1, 10)])
    live_y = _prepare(4, view=1, primary=1, reqs=[_req(1, 11)])
    # The stalled view-0 primary certified Y fresh at its stale view
    # AFTER the cluster moved on (high own counter, old view).
    stale_y = _prepare(50, view=0, primary=0, reqs=[_req(1, 11)])
    vc_live = ViewChange(
        replica_id=1, new_view=2, log=(live_x, live_y), ui=UI(counter=9)
    )
    vc_stale = ViewChange(
        replica_id=0, new_view=2, log=(stale_y,), ui=UI(counter=51)
    )
    s = vc_mod.compute_new_view_set([vc_live, vc_stale], 2)
    # X before Y — the committed order — not [Y, X] via the stale slot.
    assert [vc_mod.batch_key(p) for p in s] == [((1, 10),), ((1, 11),)]
    assert [(p.view, p.ui.counter) for p in s] == [(1, 3), (1, 4)]


def test_batch_key_and_reproposal_enforcement():
    st = vc_mod.ViewChangeState(4, 1, replica_id=2)
    a = _prepare(7, view=1, primary=1, reqs=[_req(1, 1), _req(2, 3)])
    b = _prepare(8, view=1, primary=1, reqs=[_req(1, 2)])
    st.arm_reproposals(1, [vc_mod.batch_key(a), vc_mod.batch_key(b)])
    # out-of-order re-proposal refused
    assert st.check_reproposal(b) is False
    # in-order accepted, queue drains, regime ends
    assert st.check_reproposal(a) is True
    assert st.check_reproposal(b) is True
    assert 1 not in st.reproposals
    # after the regime any prepare passes
    assert st.check_reproposal(_prepare(9, view=1, primary=1)) is True
    # regimes are per view: arming view 2 leaves view 1 unaffected
    # (concurrent NEW-VIEW applications must not overwrite each other)
    st.arm_reproposals(2, [vc_mod.batch_key(a)])
    assert st.check_reproposal(_prepare(9, view=1, primary=1)) is True
    assert st.check_reproposal(_prepare(9, view=2, primary=2)) is False


class _UIOnlyVerifier:
    """verify_ui stand-in: accepts everything, returns the UI."""

    async def __call__(self, msg):
        if msg.ui is None or msg.ui.counter == 0:
            raise api.AuthenticationError("missing UI")
        return msg.ui


def _vc_validator():
    return vc_mod.make_view_change_validator(_UIOnlyVerifier())


def test_view_change_validator_log_completeness():
    validate = _vc_validator()
    p1 = _prepare(1, primary=1)
    p2 = _prepare(2, primary=1)
    ok = ViewChange(replica_id=1, new_view=1, log=(p1, p2), ui=UI(counter=3))
    asyncio.run(validate(ok))

    # a counter gap (omitted message) is rejected
    gap = ViewChange(replica_id=1, new_view=1, log=(p1, _prepare(3, primary=1)),
                     ui=UI(counter=4))
    with pytest.raises(api.AuthenticationError, match="gap"):
        asyncio.run(validate(gap))

    # the VIEW-CHANGE's own counter must extend the log
    skip = ViewChange(replica_id=1, new_view=1, log=(p1, p2), ui=UI(counter=5))
    with pytest.raises(api.AuthenticationError, match="extend"):
        asyncio.run(validate(skip))

    # a foreign entry (not the sender's message) is rejected
    foreign = ViewChange(replica_id=1, new_view=1, log=(_prepare(1, primary=2),),
                         ui=UI(counter=2))
    with pytest.raises(api.AuthenticationError, match="another replica"):
        asyncio.run(validate(foreign))


def test_new_view_validator_quorum_shape():
    # n=4, f=1: the view-change quorum is n-f = 3, NOT f+1 = 2 — two
    # disjoint pairs could otherwise commit and recover separately (the
    # quorum must intersect every f+1 commitment quorum for all n >= 2f+1)
    validate = vc_mod.make_new_view_validator(
        4, 1, _UIOnlyVerifier(), _vc_validator()
    )
    vc1 = ViewChange(replica_id=0, new_view=1, log=(), ui=UI(counter=1))
    vc2 = ViewChange(replica_id=2, new_view=1, log=(), ui=UI(counter=1))
    vc3 = ViewChange(replica_id=3, new_view=1, log=(), ui=UI(counter=1))
    ok = NewView(replica_id=1, new_view=1, view_changes=(vc1, vc2, vc3),
                 ui=UI(counter=1))
    asyncio.run(validate(ok))
    assert vc_mod.ViewChangeState(4, 1, 0).vc_quorum == 3
    assert vc_mod.ViewChangeState(7, 3, 0).vc_quorum == 4  # n=2f+1: f+1

    # must come from view 1's primary (replica 1 of 4)
    wrong_primary = NewView(replica_id=2, new_view=1,
                            view_changes=(vc1, vc2, vc3), ui=UI(counter=1))
    with pytest.raises(api.AuthenticationError, match="primary"):
        asyncio.run(validate(wrong_primary))

    # an f+1-sized (sub-quorum) set is rejected
    small = NewView(replica_id=1, new_view=1, view_changes=(vc2, vc3),
                    ui=UI(counter=1))
    with pytest.raises(api.AuthenticationError, match="distinct"):
        asyncio.run(validate(small))

    # distinct senders required
    dup = NewView(replica_id=1, new_view=1, view_changes=(vc1, vc2, vc2),
                  ui=UI(counter=1))
    with pytest.raises(api.AuthenticationError, match="distinct"):
        asyncio.run(validate(dup))

    # embedded VCs must be for the same view
    other = ViewChange(replica_id=3, new_view=2, log=(), ui=UI(counter=1))
    mixed = NewView(replica_id=1, new_view=1, view_changes=(vc1, vc2, other),
                    ui=UI(counter=1))
    with pytest.raises(api.AuthenticationError, match="another view"):
        asyncio.run(validate(mixed))


def test_codec_rejects_nesting_bomb():
    """Crafted deep self-nesting must fail as a CodecError (a drop), not a
    RecursionError (which peers would count as a local internal bug)."""
    from minbft_tpu.messages.codec import CodecError

    p = _prepare(1, primary=1)
    msg = ViewChange(replica_id=1, new_view=1, log=(p,), ui=UI(counter=2))
    for _ in range(200):
        msg = ViewChange(replica_id=1, new_view=1, log=(msg,), ui=UI(counter=2))
    data = marshal(msg)
    with pytest.raises(CodecError, match="nesting"):
        unmarshal(data)


def test_trimmed_entries_keep_authen_bytes():
    """A trimmed prior VIEW-CHANGE authenticates identically to the full
    original (the digest substitutes for the nested log), so logs stay
    linear instead of nesting exponentially; full nested logs are refused
    by the validator."""
    from minbft_tpu.messages import authen_bytes

    p = _prepare(1, primary=1)
    full = ViewChange(replica_id=1, new_view=1, log=(p,), ui=UI(counter=2))
    trimmed = vc_mod.trim_log_entry(full)
    assert trimmed.log == () and trimmed.log_digest != b""
    assert authen_bytes(trimmed) == authen_bytes(full)
    # codec round trip preserves the carried digest
    again = unmarshal(marshal(trimmed))
    assert authen_bytes(again) == authen_bytes(full)
    # prepares/commits pass through untouched
    assert vc_mod.trim_log_entry(p) is p

    validate = _vc_validator()
    nested_full = ViewChange(
        replica_id=1, new_view=2,
        log=(p, ViewChange(replica_id=1, new_view=1, log=(p,), ui=UI(counter=2))),
        ui=UI(counter=3),
    )
    with pytest.raises(api.AuthenticationError, match="trimmed"):
        asyncio.run(validate(nested_full))
    nested_trimmed = ViewChange(
        replica_id=1, new_view=2, log=(p, trimmed), ui=UI(counter=3)
    )
    asyncio.run(validate(nested_trimmed))


def test_demand_window_bounds_memory():
    st = vc_mod.ViewChangeState(4, 1, replica_id=0)
    assert st.in_window(1, 0)
    assert st.in_window(st.MAX_VIEWS_AHEAD, 0)
    assert not st.in_window(st.MAX_VIEWS_AHEAD + 1, 0)
    assert not st.in_window(0, 0)  # stale
    assert not st.in_window(5, 5)


def test_codec_round_trip():
    p = _prepare(1)
    c = Commit(replica_id=1, prepare=p, ui=UI(counter=1, cert=b"d"))
    vc = ViewChange(replica_id=1, new_view=1, log=(p, c), ui=UI(counter=2, cert=b"e"))
    nv = NewView(replica_id=1, new_view=1, view_changes=(vc,), ui=UI(counter=3, cert=b"f"))
    for m in (vc, nv):
        again = unmarshal(marshal(m))
        assert marshal(again) == marshal(m)


# ---------------------------------------------------------------------------
# The money test: the cluster survives a crashed primary.


def test_cluster_survives_primary_crash():
    """n=4/f=1: commit in view 0, crash the primary, commit again — the
    request timeout demands a view change, f+1 demands trigger
    VIEW-CHANGEs, the new primary (1) issues NEW-VIEW, and the pending
    request commits in view 1 (the reference can only demonstrate backup
    crashes, README.md:411-458 — primary crash wedges it)."""

    async def scenario():
        from minbft_tpu.client import new_client
        from minbft_tpu.sample.config import SimpleConfiger
        from minbft_tpu.sample.conn.inprocess import InProcessClientConnector

        cfg = SimpleConfiger(
            n=4, f=1,
            timeout_request=0.8, timeout_prepare=0.4, timeout_viewchange=3.0,
        )
        # ECDSA USIG with TOFU (key-material) anchors exercises the epoch
        # capture machinery: the new primary must verify its OWN UIs
        # inside peers' COMMITs, which needs the constructor-seeded
        # self-anchor (caught live over sockets; full pinned IDs mask it).
        replicas, c_auths, stubs, ledgers = await make_cluster(
            n=4, f=1, cfg=cfg, usig_kind="ecdsa", tofu_anchors=True
        )
        client = new_client(0, 4, 1, c_auths[0], InProcessClientConnector(stubs))
        await client.start()
        try:
            r0 = await asyncio.wait_for(client.request(b"before-crash"), 30)
            assert r0

            # crash the view-0 primary: kill its streams AND its tasks
            stubs[0].crash()
            await replicas[0].stop()

            r1 = await asyncio.wait_for(client.request(b"after-crash"), 30)
            assert r1

            # survivors entered view 1 and committed both requests
            for r in replicas[1:]:
                cur, _ = await r.handlers.view_state.hold_view()
                assert cur >= 1, f"replica {r.id} still in view {cur}"
            deadline = asyncio.get_running_loop().time() + 10
            while asyncio.get_running_loop().time() < deadline:
                if all(lg.length >= 2 for lg in ledgers[1:]):
                    break
                await asyncio.sleep(0.05)
            lengths = [lg.length for lg in ledgers[1:]]
            assert all(l == 2 for l in lengths), lengths
            # one more request in the new view works normally
            r2 = await asyncio.wait_for(client.request(b"steady-state"), 30)
            assert r2
        finally:
            await client.stop()
            for r in replicas[1:]:
                await r.stop()
        return True

    assert asyncio.run(scenario())


def test_the_view_change_leaves_its_steps_on_the_timeline(monkeypatch):
    """The same crash of the view-0 primary, read from the process
    timeline: for view 1 a demand or more, a ``started`` and an
    ``entered`` row from each survivor, one ``new_view_sent`` from the new
    primary (replica 1), each in protocol order; and per survivor the
    certificate checks its two validators handed to the authenticator, as
    a counting stand-in around the replica's UI verifier saw them while a
    VIEW-CHANGE or NEW-VIEW was being validated."""
    from minbft_tpu.core import usig_ui
    from minbft_tpu.obs import trace

    validating = contextvars.ContextVar("validating", default=False)
    seen = []  # [checks] per Handlers, in the order the replicas are built
    build_verifier = usig_ui.make_ui_verifier
    build_vc = vc_mod.make_view_change_validator
    build_nv = vc_mod.make_new_view_validator

    def verifier(authenticator):
        verify_ui, checks = build_verifier(authenticator), [0]
        seen.append(checks)

        async def verify(msg):
            checks[0] += validating.get()
            return await verify_ui(msg)

        return verify

    def marked(validate):
        async def validate_marked(msg):
            token = validating.set(True)
            try:
                await validate(msg)
            finally:
                validating.reset(token)

        return validate_marked

    monkeypatch.setattr(usig_ui, "make_ui_verifier", verifier)
    monkeypatch.setattr(vc_mod, "make_view_change_validator", lambda *a: marked(build_vc(*a)))
    monkeypatch.setattr(vc_mod, "make_new_view_validator", lambda *a: marked(build_nv(*a)))

    def items_by_replica(view, since):
        out = {}
        for r, v, n, t in trace.timeline()["viewchange"]["verify_items"]:
            if v == view and t >= since:
                out[r] = out.get(r, 0) + n
        return out

    async def scenario():
        from minbft_tpu.client import new_client
        from minbft_tpu.sample.config import SimpleConfiger
        from minbft_tpu.sample.conn.inprocess import InProcessClientConnector

        cfg = SimpleConfiger(
            n=4, f=1,
            timeout_request=0.8, timeout_prepare=0.4, timeout_viewchange=3.0,
        )
        replicas, c_auths, stubs, _ledgers = await make_cluster(n=4, f=1, cfg=cfg)
        client = new_client(0, 4, 1, c_auths[0], InProcessClientConnector(stubs))
        await client.start()
        try:
            assert await asyncio.wait_for(client.request(b"before-crash"), 30)
            t0 = time.monotonic_ns()  # earlier cases of this process changed views too
            stubs[0].crash()
            await replicas[0].stop()
            assert await asyncio.wait_for(client.request(b"after-crash"), 30)
            await all_entered(replicas[1:], 1)
            items = items_by_replica(1, t0)
        finally:
            await client.stop()
            for r in replicas[1:]:
                await r.stop()
        rows = [row for row in trace.timeline()["viewchange"]["rows"] if row[3] >= t0]
        return rows, {r: items.get(r, 0) for r in range(4)}

    rows, items = asyncio.run(scenario())
    assert {view for _r, view, _s, _t in rows} == {1}
    at = {}
    for replica, _view, stage, t in rows:
        at.setdefault((replica, stage), []).append(t)
    survivors = (1, 2, 3)
    demands = sorted(t for (r, stage), ts in at.items() if stage == "demand" for t in ts)
    assert demands and all(r in survivors for r, stage in at if stage == "demand")
    for r in survivors:
        (started,), (entered,) = at[r, "started"], at[r, "entered"]
        assert demands[0] <= started < entered
    (sent,) = at[1, "new_view_sent"]
    assert [r for r, stage in at if stage == "new_view_sent"] == [1]
    assert at[1, "started"][0] <= sent <= at[1, "entered"][0]
    assert not any(r == 0 for r, _stage in at)  # the crashed primary wrote nothing
    assert [items[r] for r in survivors] == [seen[r][0] for r in survivors]
    assert all(items[r] > 0 for r in survivors) and items[0] == 0


def test_a_window_without_a_view_change_writes_no_view_change_row():
    """The view-change rings are written on the view-change path alone: a
    cluster that commits without one leaves them as they were."""
    from minbft_tpu.obs import trace

    async def scenario():
        from minbft_tpu.client import new_client
        from minbft_tpu.sample.conn.inprocess import InProcessClientConnector

        t0 = time.monotonic_ns()
        replicas, c_auths, stubs, _ledgers = await make_cluster(n=4, f=1)
        client = new_client(0, 4, 1, c_auths[0], InProcessClientConnector(stubs))
        await client.start()
        try:
            for k in range(3):
                assert await asyncio.wait_for(client.request(b"w%d" % k), 30)
        finally:
            await client.stop()
            for r in replicas:
                await r.stop()
        section = trace.timeline()["viewchange"]
        return [row for row in section["rows"] + section["verify_items"] if row[3] >= t0]

    assert asyncio.run(scenario()) == []


async def all_entered(replicas, view, timeout=10.0):
    """Wait until every one of ``replicas`` stands in ``view``: a request is
    answered by f+1, and the others may enter the view a moment later."""
    loop = asyncio.get_running_loop()
    deadline = loop.time() + timeout
    while not all(r.metrics.current_view >= view for r in replicas):
        assert loop.time() < deadline, [r.metrics.current_view for r in replicas]
        await asyncio.sleep(0.02)


def test_view_change_escalates_past_faulty_new_primary():
    """n=7/f=3: crash the primary AND the next primary — the view-change
    timeout escalates the demand past the dead candidate until a live one
    (replica 2, view 2) completes the transition."""

    async def scenario():
        from minbft_tpu.client import new_client
        from minbft_tpu.sample.config import SimpleConfiger
        from minbft_tpu.sample.conn.inprocess import InProcessClientConnector

        cfg = SimpleConfiger(
            n=7, f=3,
            timeout_request=0.8, timeout_prepare=0.4, timeout_viewchange=1.5,
        )
        replicas, c_auths, stubs, ledgers = await make_cluster(
            n=7, f=3, cfg=cfg
        )
        client = new_client(0, 7, 3, c_auths[0], InProcessClientConnector(stubs))
        await client.start()
        try:
            assert await asyncio.wait_for(client.request(b"view0"), 30)
            for dead in (0, 1):
                stubs[dead].crash()
                await replicas[dead].stop()
            assert await asyncio.wait_for(client.request(b"view2"), 60)
            views = []
            for r in replicas[2:]:
                cur, _ = await r.handlers.view_state.hold_view()
                views.append(cur)
            assert all(v >= 2 for v in views), views
        finally:
            await client.stop()
            for r in replicas[2:]:
                await r.stop()
        return True

    assert asyncio.run(scenario())


# ---------------------------------------------------------------------------
# Checkpoint-truncated VIEW-CHANGE validation (phase 2): a Byzantine sender
# must not be able to hide evidence behind an unprovable truncation base or
# an uncovered stub — the coverage-bound audit is what keeps GC safe at
# n = 2f+1 where quorum intersections can be entirely Byzantine.


def _cp_claim(replica, bounds, count=100, view=0, cv=50, digest=b"D" * 32):
    from minbft_tpu.messages import Checkpoint

    return Checkpoint(
        replica_id=replica, count=count, view=view, cv=cv, digest=digest,
        bounds=tuple(sorted(bounds.items())), signature=b"sig",
    )


def _truncating_validator(f=1):
    from minbft_tpu.core import checkpoint as cp_mod

    async def verify_signature(msg):
        return None

    cert_validator = cp_mod.make_cert_validator(f, verify_signature)
    return vc_mod.make_view_change_validator(_UIOnlyVerifier(), cert_validator)


def test_truncated_vc_requires_provable_base():
    validate = _truncating_validator()
    entry = _prepare(11, primary=1)
    entry.ui.counter = 11  # retained suffix starts above the base

    # base 10 without any certificate: rejected
    bare = ViewChange(
        replica_id=1, new_view=1, log=(entry,), ui=UI(counter=12),
        log_base=10,
    )
    with pytest.raises(api.AuthenticationError, match="certificate"):
        asyncio.run(validate(bare))

    # certificate whose coverage bounds for the sender stop short of the
    # base: the dropped prefix is NOT provably covered -> rejected
    weak_cert = (
        _cp_claim(2, {1: 4}),
        _cp_claim(3, {1: 10}),
    )
    weak = ViewChange(
        replica_id=1, new_view=1, log=(entry,), ui=UI(counter=12),
        log_base=10, checkpoint_cert=weak_cert,
    )
    with pytest.raises(api.AuthenticationError, match="not provably covered"):
        asyncio.run(validate(weak))

    # f+1 claims all attesting bounds >= base: accepted
    good_cert = (
        _cp_claim(2, {1: 10}),
        _cp_claim(3, {1: 12}),
    )
    good = ViewChange(
        replica_id=1, new_view=1, log=(entry,), ui=UI(counter=12),
        log_base=10, checkpoint_cert=good_cert,
    )
    asyncio.run(validate(good))

    # ...but the retained counters must still extend the base contiguously
    gap = ViewChange(
        replica_id=1, new_view=1, log=(entry,), ui=UI(counter=12),
        log_base=9, checkpoint_cert=good_cert,
    )
    with pytest.raises(api.AuthenticationError, match="gap"):
        asyncio.run(validate(gap))


def test_vc_stub_must_be_covered_by_certificate():
    from minbft_tpu.messages.authen import collection_digest

    validate = _truncating_validator()
    cert = (_cp_claim(2, {1: 0}), _cp_claim(3, {1: 0}))  # position (0, 50)

    def stub_commit(counter, batch_cv):
        # The sender's COMMIT at its own ``counter``, embedding the
        # PRIMARY's prepare for batch ``batch_cv`` stubbed down to its
        # digest — the shape truncation actually produces.
        full = _prepare(batch_cv, primary=0)
        stub_p = Prepare(
            replica_id=0, view=0, requests=(),
            ui=UI(counter=batch_cv, cert=b"c"),
            requests_digest=collection_digest(full.requests, b""),
        )
        return Commit(replica_id=1, prepare=stub_p, ui=UI(counter=counter, cert=b"c"))

    # a stubbed commit to batch cv 40 <= certified cv 50: covered, accepted
    covered = ViewChange(
        replica_id=1, new_view=1, log=(stub_commit(1, 40),),
        ui=UI(counter=2), checkpoint_cert=cert,
    )
    asyncio.run(validate(covered))

    # batch cv 60 > certified 50: stubbing it would hide LIVE commit
    # evidence -> rejected
    uncovered = ViewChange(
        replica_id=1, new_view=1, log=(stub_commit(1, 60),),
        ui=UI(counter=2), checkpoint_cert=cert,
    )
    with pytest.raises(api.AuthenticationError, match="does not cover"):
        asyncio.run(validate(uncovered))

    # a stub with NO certificate at all: nothing proves coverage
    naked = ViewChange(
        replica_id=1, new_view=1, log=(stub_commit(1, 40),),
        ui=UI(counter=2),
    )
    with pytest.raises(api.AuthenticationError, match="does not cover"):
        asyncio.run(validate(naked))


def test_checkpoint_cert_validator_shape():
    """The certificate itself: f+1 distinct matching signature-verified
    claims — mismatches, duplicates, and short certs are refused."""
    from minbft_tpu.core import checkpoint as cp_mod

    async def verify_signature(msg):
        return None

    validate_cert = cp_mod.make_cert_validator(1, verify_signature)

    ok = (_cp_claim(2, {1: 5}), _cp_claim(3, {1: 7}))
    assert asyncio.run(validate_cert(ok)).count == 100

    with pytest.raises(api.AuthenticationError, match="f\\+1"):
        asyncio.run(validate_cert((_cp_claim(2, {1: 5}),)))

    with pytest.raises(api.AuthenticationError, match="duplicate"):
        asyncio.run(validate_cert((_cp_claim(2, {1: 5}), _cp_claim(2, {1: 6}))))

    with pytest.raises(api.AuthenticationError, match="match"):
        asyncio.run(
            validate_cert(
                (_cp_claim(2, {1: 5}), _cp_claim(3, {1: 5}, digest=b"X" * 32))
            )
        )
