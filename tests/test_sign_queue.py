"""The batched device signing pipeline.

Three layers, mirroring the verify path's test structure:

- ops: differential fuzz pinning ``sign_batch`` bit-identity against the
  hostcrypto signers for both schemes (adversarial digests included), and
  the exceptional-lane / RFC 6979 retry fallbacks;
- engine: the ``_SignQueue`` — memo-FREE by design (every sign occupies
  its own lane; the dedup shortcuts of ``_SchemeQueue`` must be absent),
  host fallback on CPU / write-off / hung dispatch, stats accounting;
- authenticator: CLIENT/REPLICA signing routes through the queue, USIG
  UI signing provably never does (counter-after-sign is serial,
  reference usig.c:66-69).

All device-path tests share ONE bucket shape (``_BUCKET``) so the comb
kernels compile once per scheme per process (cached persistently by
conftest's compilation cache).
"""

import asyncio
import hashlib
import threading

import numpy as np
import pytest

from minbft_tpu import api
from minbft_tpu.ops import ed25519 as ed
from minbft_tpu.ops import p256
from minbft_tpu.parallel import BatchVerifier
from minbft_tpu.utils import hostcrypto as hc

_BUCKET = 16


# ---------------------------------------------------------------------------
# ops: differential fuzz vs the host signers


def _adversarial_digests():
    """Digest edge cases: z == 0 (mod n), z == n - 1, all-ones (> n as an
    int), leading-zero bytes, and the reduction boundary n itself."""
    return [
        b"\x00" * 32,
        b"\xff" * 32,
        p256.N.to_bytes(32, "big"),  # z % N == 0
        (p256.N - 1).to_bytes(32, "big"),
        b"\x00" * 31 + b"\x01",
    ]


def test_ecdsa_sign_batch_differential_fuzz():
    items, pubs = [], []
    for i in range(_BUCKET - len(_adversarial_digests())):
        d, q = hc.keygen()
        items.append((d, hashlib.sha256(b"fuzz-%d" % i).digest()))
        pubs.append(q)
    d, q = hc.keygen()
    for dg in _adversarial_digests():
        items.append((d, dg))
        pubs.append(q)

    got = p256.sign_batch(items, bucket=_BUCKET)
    for (priv, dg), sig, q in zip(items, got, pubs):
        # byte-identity with the deterministic host signer...
        assert sig == hc.ecdsa_sign_py(priv, dg)
        # ...and acceptance by the independent host verifier
        assert hc.ecdsa_verify(q, dg, sig)


def test_ed25519_sign_batch_differential_fuzz():
    seeds = [hashlib.sha256(b"seed-%d" % i).digest() for i in range(3)]
    msgs = [
        b"",  # empty message
        b"m",
        b"x" * 1000,  # long message
        hashlib.sha256(b"d").digest(),
        b"\x00" * 64,
    ]
    # one-signer-many-messages (the production shape, exercises the
    # per-seed derivation cache) plus a seed mix
    items = [(seeds[0], m) for m in msgs]
    items += [(seeds[i % 3], b"mix-%d" % i) for i in range(_BUCKET - len(items))]

    got = ed.sign_batch(items, bucket=_BUCKET)
    for (seed, msg), sig in zip(items, got):
        assert sig == hc.ed25519_sign(seed, msg)
        pub = hc.ed25519_keygen(seed)[1]
        assert hc.ed25519_verify(pub, msg, sig)


def test_ecdsa_exceptional_lane_falls_back_to_serial_signer():
    """The Z == 0 lane fallback — the same serial path the
    vanishing-probability RFC 6979 r == 0 / s == 0 retries take: a stub
    kernel that reports every lane exceptional must still yield
    byte-correct signatures via hc.ecdsa_sign_py."""
    items = [
        (hc.keygen()[0], hashlib.sha256(b"exc-%d" % i).digest())
        for i in range(4)
    ]

    def dead_kernel(k_arr):
        return np.zeros((len(k_arr), 2, 16), np.uint16)  # Z == 0 everywhere

    got = p256.sign_batch(items, bucket=len(items), kg_kernel=dead_kernel)
    assert got == [hc.ecdsa_sign_py(d, dg) for d, dg in items]


def _rfc6979_first_candidate(d: int, z: int, order: int) -> int:
    """The DRBG's FIRST candidate, reconstructed independently (RFC 6979
    §3.2 steps a-g) — lets the test detect that the retry loop ran."""
    import hmac as hmac_mod

    x = d.to_bytes(32, "big")
    h1 = (z % order).to_bytes(32, "big")
    v = b"\x01" * 32
    k = b"\x00" * 32
    k = hmac_mod.new(k, v + b"\x00" + x + h1, hashlib.sha256).digest()
    v = hmac_mod.new(k, v, hashlib.sha256).digest()
    k = hmac_mod.new(k, v + b"\x01" + x + h1, hashlib.sha256).digest()
    v = hmac_mod.new(k, v, hashlib.sha256).digest()
    v = hmac_mod.new(k, v, hashlib.sha256).digest()
    return int.from_bytes(v, "big")


def test_rfc6979_nonce_retry_loop():
    """The candidate >= order retry branch of the RFC 6979 DRBG: with
    order = 2^255 roughly half of all 256-bit candidates are out of
    range, so some z values MUST take the retry branch — the result must
    land in [1, order) and stay deterministic.  (The implementation
    draws full 256-bit candidates, sized for the ~2^256 curve orders it
    serves — a tiny order would practically never terminate, which is
    also why this test reconstructs the first candidate instead.)"""
    order = 1 << 255
    retried = False
    for z in range(16):
        k = hc._rfc6979_k(3, z, order=order)
        assert 1 <= k < order
        assert k == hc._rfc6979_k(3, z, order=order)  # deterministic
        first = _rfc6979_first_candidate(3, z, order)
        if not 1 <= first < order:
            retried = True
            assert k != first  # the rejected candidate was not returned
        else:
            assert k == first
    assert retried, "no z exercised the retry branch (order choice broken)"


def test_sign_prepare_staging_buffer_identity():
    """sign_prepare writing into a recycled engine staging buffer must
    produce exactly what the allocate-fresh path produces, pad lanes
    included (k = 1 tail)."""
    items = [
        (hc.keygen()[0], hashlib.sha256(b"st-%d" % i).digest())
        for i in range(5)
    ]
    fresh, meta_f = p256.sign_prepare(items, _BUCKET)
    out = np.full((_BUCKET, p256.SIGN_COLS), 0xABCD, np.uint16)  # dirty
    staged, meta_s = p256.sign_prepare(items, _BUCKET, out=out)
    assert staged is out
    assert np.array_equal(fresh, staged)
    assert meta_f == meta_s
    assert (staged[5:, 0] == 1).all() and (staged[5:, 1:] == 0).all()

    e_fresh, e_meta = ed.sign_prepare([(b"\x07" * 32, b"m")], 4)
    e_out = np.full((4, ed.SIGN_COLS), 0xEEEE, np.uint16)
    e_staged, e_meta2 = ed.sign_prepare([(b"\x07" * 32, b"m")], 4, out=e_out)
    assert np.array_equal(e_fresh, e_staged)
    assert e_meta == e_meta2


# ---------------------------------------------------------------------------
# engine: the _SignQueue


def test_sign_queue_device_path_concurrent_hammer_memo_free():
    """Concurrent submits — including byte-identical DUPLICATES — through
    the DEVICE path: every submission must occupy its own lane (items
    counts them all), results must all be correct, and none of
    _SchemeQueue's dedup machinery may exist on the sign queue."""

    async def scenario():
        eng = BatchVerifier(
            max_batch=_BUCKET, buckets=(_BUCKET,), sign_on_device=True
        )
        d, q = hc.keygen()
        dg = hashlib.sha256(b"dup").digest()
        n_dups, n_uniq = 24, 12
        dup_futs = [eng.sign_ecdsa_p256(d, dg) for _ in range(n_dups)]
        uniq_items = [
            (d, hashlib.sha256(b"uniq-%d" % i).digest()) for i in range(n_uniq)
        ]
        uniq_futs = [eng.sign_ecdsa_p256(di, dgi) for di, dgi in uniq_items]
        dup_sigs = await asyncio.gather(*dup_futs)
        uniq_sigs = await asyncio.gather(*uniq_futs)

        expected = hc.ecdsa_sign_py(d, dg)
        assert all(s == expected for s in dup_sigs)
        for (di, dgi), s in zip(uniq_items, uniq_sigs):
            assert s == hc.ecdsa_sign_py(di, dgi)

        sq = eng._sign_queues["ecdsa_p256"]
        st = sq.stats
        # memo-free: EVERY submission (duplicates included) took a lane
        assert st.items == n_dups + n_uniq
        assert st.host_fallback_items == 0  # genuinely the device path
        assert st.batches >= 2  # the hammer overflowed one bucket
        # the dedup shortcuts of _SchemeQueue must be structurally absent
        for attr in ("_memo", "_neg_memo", "_inflight_futs"):
            assert not hasattr(sq, attr), attr
        assert not hasattr(st, "memo_hits")
        assert st.host_prep_time_s > 0 and st.device_time_s > 0
        assert st.padded_lanes > 0  # bucket padding accounted
        return True

    assert asyncio.run(scenario())


def test_sign_queue_cpu_backend_falls_back_to_host():
    """Auto placement on the CPU backend: the queue transparently signs
    on host and RECORDS it — host_fallback_items equals the demand, so a
    bench artifact can never read host signs as device throughput."""

    async def scenario():
        eng = BatchVerifier(max_batch=8, buckets=(8,))  # sign_on_device=auto
        seed, pub = hc.ed25519_keygen(b"\x11" * 32)
        msgs = [b"fb-%d" % i for i in range(10)]
        sigs = await asyncio.gather(
            *[eng.sign_ed25519(seed, m) for m in msgs]
        )
        for m, s in zip(msgs, sigs):
            assert s == hc.ed25519_sign(seed, m)
            assert hc.ed25519_verify(pub, m, s)
        st = eng.sign_stats["ed25519"]
        assert st.items == 10
        assert st.host_fallback_items == 10  # all host, all recorded
        assert st.dispatch_timeouts == 0  # no timeout machinery armed
        return True

    assert asyncio.run(scenario())


def test_sign_queue_hung_dispatch_falls_back_and_writes_off():
    """The liveness net, sign-side: a hung device dispatch resolves via
    the host signer after dispatch_timeout, repeated hangs write the
    device off, and the fallback items are counted."""

    async def scenario():
        eng = BatchVerifier(
            max_batch=8, dispatch_timeout=0.2, sign_on_device=True
        )
        hang = threading.Event()

        def hanging_dispatch(items):
            hang.wait(30)
            raise AssertionError("unreachable in test")

        d, pub = hc.keygen()
        sq = eng._sign_queue("ecdsa_p256", hanging_dispatch)
        sq._device_ever_succeeded = True  # no cold-compile headroom

        dg = hashlib.sha256(b"hung").digest()
        sig = await asyncio.wait_for(sq.submit((d, dg)), 10)
        assert hc.ecdsa_verify(pub, dg, sig)  # host-signed, still valid
        assert sq.stats.dispatch_timeouts == 1
        assert sq.stats.host_fallback_items == 1

        for i in range(2):
            await asyncio.wait_for(
                sq.submit((d, hashlib.sha256(b"h%d" % i).digest())), 10
            )
        assert sq._device_written_off
        # written off: straight to host, no timeout wait
        t0 = asyncio.get_running_loop().time()
        await asyncio.wait_for(sq.submit((d, dg)), 10)
        assert asyncio.get_running_loop().time() - t0 < 0.15
        assert sq.stats.host_fallback_items == 4
        hang.set()
        return True

    assert asyncio.run(scenario())


@pytest.mark.parametrize("how", ["device", "fallback", "timeout"])
def test_sign_dispatch_rows_one_per_counted_batch(how):
    """The sign queues' side of the dispatch record: rows == batches on
    the device path, on the host fallback and across a hung dispatch,
    the eight instants never decreasing, the flags saying which it was."""
    from minbft_tpu.obs import trace as obs_trace

    cols = obs_trace.DISPATCH_COLUMNS

    async def scenario():
        d, _pub = hc.keygen()
        digests = [hashlib.sha256(b"row-%d" % i).digest() for i in range(20)]
        if how == "device":
            eng = BatchVerifier(max_batch=_BUCKET, buckets=(_BUCKET,), sign_on_device=True)
            await asyncio.gather(*[eng.sign_ecdsa_p256(d, dg) for dg in digests])
            want = 0
        elif how == "fallback":
            eng = BatchVerifier(max_batch=_BUCKET, buckets=(_BUCKET,))  # auto: host on CPU
            await asyncio.gather(*[eng.sign_ecdsa_p256(d, dg) for dg in digests])
            want = obs_trace.FLAG_FALLBACK | obs_trace.FLAG_NO_DEVICE
        else:
            eng = BatchVerifier(max_batch=8, dispatch_timeout=0.1, sign_on_device=True)
            hang = threading.Event()
            sq = eng._sign_queue("ecdsa_p256", lambda items: hang.wait(30))
            sq._device_ever_succeeded = True  # no cold-compile headroom
            await asyncio.wait_for(sq.submit((d, digests[0])), 10)
            hang.set()
            want = (obs_trace.FLAG_FALLBACK | obs_trace.FLAG_TIMEOUT
                    | obs_trace.FLAG_NO_DEVICE)
        st = eng.sign_stats["ecdsa_p256"]
        rows = [dict(zip(cols, e)) for e in eng.drain_obs_events()]
        assert len(rows) == st.batches > 0
        assert sum(r["items"] for r in rows) == st.items
        for r in rows:
            assert (r["queue"], r["kind"]) == ("sign_ecdsa_p256", "sign")
            assert r["flags"] == want
            t = [r[c] for c in cols[8:]]
            assert t == sorted(t) and t[0] > 0
            if how == "device":
                # sign has a finish phase on the worker, after the result
                assert r["t_finish_end"] > r["t_result"] > r["t_launch_end"]
                assert r["lanes"] == _BUCKET
        return True

    assert asyncio.run(scenario())


# ---------------------------------------------------------------------------
# authenticator: routing and the serial-USIG boundary


def test_authenticator_routes_client_replica_signs_through_queue():
    from minbft_tpu.sample.authentication.authenticator import (
        SampleAuthenticator,
    )

    async def scenario():
        eng = BatchVerifier(max_batch=8, buckets=(8,))
        d_r, _ = hc.keygen()
        d_c, _ = hc.keygen()
        auth = SampleAuthenticator(
            replica_priv=d_r, client_priv=d_c, engine=eng
        )
        tag = await auth.generate_message_authen_tag_async(
            api.AuthenticationRole.REPLICA, b"reply-bytes"
        )
        assert len(tag) == 64
        assert eng.sign_stats["ecdsa_p256"].items == 1
        tag = await auth.generate_message_authen_tag_async(
            api.AuthenticationRole.CLIENT, b"request-bytes"
        )
        assert len(tag) == 64
        assert eng.sign_stats["ecdsa_p256"].items == 2
        # batch_sign=False: same call, queue untouched
        auth_off = SampleAuthenticator(
            replica_priv=d_r, engine=eng, batch_sign=False
        )
        await auth_off.generate_message_authen_tag_async(
            api.AuthenticationRole.REPLICA, b"x"
        )
        assert eng.sign_stats["ecdsa_p256"].items == 2
        return True

    assert asyncio.run(scenario())


def test_usig_signing_never_touches_the_sign_queue():
    """The serial-USIG boundary (acceptance): UI creation — sync AND
    async surfaces — must produce zero sign-queue traffic.  The USIG
    counter is incremented only after the certificate exists
    (reference usig.c:66-69); routing it through a batch queue would
    break that discipline."""
    from minbft_tpu.sample.authentication.authenticator import (
        SampleAuthenticator,
    )
    from minbft_tpu.usig.software import EcdsaUSIG

    async def scenario():
        eng = BatchVerifier(max_batch=8, buckets=(8,))
        usig = EcdsaUSIG()
        d_r, _ = hc.keygen()
        auth = SampleAuthenticator(
            replica_priv=d_r,
            usig=usig,
            usig_ids={0: usig.id()},
            own_replica_id=0,
        )
        auth._engine = eng  # engine present, sign queue armed
        counters = []
        for surface in ("sync", "async"):
            for _ in range(3):
                if surface == "sync":
                    tag = auth.generate_message_authen_tag(
                        api.AuthenticationRole.USIG, b"certify-me"
                    )
                else:
                    tag = await auth.generate_message_authen_tag_async(
                        api.AuthenticationRole.USIG, b"certify-me"
                    )
                counters.append(int.from_bytes(tag[:8], "big"))
        # serial counter discipline held: strictly consecutive, no gaps
        assert counters == list(range(counters[0], counters[0] + 6))
        # and NO sign-queue traffic — not even an instantiated queue
        assert eng._sign_queues == {}
        assert eng.sign_stats == {}
        return True

    assert asyncio.run(scenario())


def test_reply_buffering_survives_out_of_order_sign_completion():
    """Review pin: two executions whose REPLY signatures complete out of
    order (concurrent sign batches — e.g. one falls back after a timeout
    while the next is device-fast) must still buffer in EXECUTION order:
    ClientState.add_reply drops a lower seq arriving after a higher one
    as a stale retry, so unordered buffering would permanently lose the
    earlier reply."""
    from minbft_tpu.core import request as request_mod
    from minbft_tpu.core.internal.clientstate import ClientStates
    from minbft_tpu.messages import Request

    async def scenario():
        loop = asyncio.get_running_loop()
        gates = {4: loop.create_future(), 5: loop.create_future()}

        async def gated_sign(msg):
            await gates[msg.seq]
            msg.signature = b"sig"

        states = ClientStates()

        class Consumer:
            async def deliver(self, op):
                return b"r"

            def state_digest(self):
                return b""

        class Pending:
            def remove(self, r):
                pass

        execute = request_mod.make_request_executor(
            0,
            lambda r: True,
            Pending(),
            lambda r: None,
            Consumer(),
            gated_sign,
            lambda reply: states.client(reply.client_id).add_reply(
                reply.seq, reply
            ),
        )
        r4 = Request(client_id=1, seq=4, operation=b"a")
        r5 = Request(client_id=1, seq=5, operation=b"b")
        await execute(r4)
        await execute(r5)
        gates[5].set_result(None)  # seq 5's signature completes FIRST
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        gates[4].set_result(None)
        reply4 = await asyncio.wait_for(states.client(1).reply_for(4), 5)
        reply5 = await asyncio.wait_for(states.client(1).reply_for(5), 5)
        assert reply4 is not None and reply4.seq == 4  # NOT dropped
        assert reply5 is not None and reply5.seq == 5
        return True

    assert asyncio.run(scenario())


def test_client_broadcasts_requests_in_seq_order_despite_sign_reordering():
    """Review pin: replica-side retirement has watermark-jump semantics
    (executing seq k supersedes this client's lower seqs), so a client
    whose batch-signed signatures resolve out of order must STILL
    broadcast its ordered requests in seq order — the send gate, not the
    signer, owns the wire order."""
    from minbft_tpu.client.client import Client
    from minbft_tpu.messages import unmarshal

    class GatedAuth(api.Authenticator):
        def __init__(self):
            self.gates = []

        def generate_message_authen_tag(self, role, msg, audience=-1):
            return b"sig"

        async def generate_message_authen_tag_async(
            self, role, msg, audience=-1
        ):
            fut = asyncio.get_running_loop().create_future()
            self.gates.append(fut)
            await fut
            return b"sig"

        async def verify_message_authen_tag(self, role, peer_id, msg, tag):
            return None

    class _Silent(api.MessageStreamHandler):
        def handle_message_stream(self, in_stream):
            async def gen():
                await asyncio.sleep(3600)
                yield b""  # pragma: no cover

            return gen()

    class _Conn(api.ReplicaConnector):
        def replica_message_stream_handler(self, replica_id):
            return _Silent()

    async def scenario():
        auth = GatedAuth()
        client = Client(0, 1, 0, auth, _Conn(), seq_start=100)
        await client.start()
        sent = []
        client._broadcast = lambda data: sent.append(unmarshal(data).seq)
        t1 = asyncio.ensure_future(client.request(b"a"))
        await asyncio.sleep(0)
        t2 = asyncio.ensure_future(client.request(b"b"))
        await asyncio.sleep(0)
        assert len(auth.gates) == 2
        auth.gates[1].set_result(None)  # the SECOND request signs first
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        assert sent == []  # gated: seq 102 must not jump ahead
        auth.gates[0].set_result(None)
        for _ in range(10):
            await asyncio.sleep(0)
        assert sent == [101, 102]  # wire order == seq order
        t1.cancel()
        t2.cancel()
        await asyncio.gather(t1, t2, return_exceptions=True)
        await client.stop()
        return True

    assert asyncio.run(scenario())


def test_cluster_replies_signed_through_sign_queue():
    """End-to-end: an engine-wired cluster commits requests while REPLY
    signing rides the sign queue (host fallback on the CPU backend —
    recorded, not hidden) and the ledger invariants hold."""
    from minbft_tpu.client import new_client
    from minbft_tpu.sample.conn.inprocess import InProcessClientConnector
    from conftest import make_cluster

    async def scenario():
        engines = [
            BatchVerifier(max_batch=32, max_delay=0.005) for _ in range(3)
        ]
        replicas, c_auths, stubs, ledgers = await make_cluster(
            n=3,
            f=1,
            usig_kind="hmac",
            engines=engines,
            batch_signatures=False,  # verify placement as the CPU SIM
            # cluster test — signing still routes through the sign queue
        )
        client = new_client(
            0, 3, 1, c_auths[0], InProcessClientConnector(stubs), seq_start=0
        )
        await client.start()
        for i in range(4):
            res = await asyncio.wait_for(client.request(b"op-%d" % i), 30)
            assert res is not None
        # every replica signed its replies through the queue (the client
        # resolves on f+1 matching replies, so the slowest replica's
        # sign task may still be in flight — poll to convergence)
        def signed_total():
            return sum(
                e.sign_stats.get("ecdsa_p256").items
                for e in engines
                if e.sign_stats.get("ecdsa_p256")
            )

        for _ in range(100):
            if signed_total() >= 4 * 3:
                break
            await asyncio.sleep(0.02)
        assert signed_total() >= 4 * 3  # n replicas x requests (at least)
        for e in engines:
            st = e.sign_stats["ecdsa_p256"]
            # CPU backend: the fallback is recorded item-for-item
            assert st.host_fallback_items == st.items
        await client.stop()
        for r in replicas:
            await r.stop()
        return True

    assert asyncio.run(scenario())
