"""Batch-ingest runtime tests (ISSUE 6).

- Differential fuzz: ``messages.codec.unmarshal_batch`` vs the scalar
  ``unmarshal`` oracle over 1000+ random well-formed AND corrupted
  frames — corrupt frames must fail ITEM-WISE, never poison the bundle
  (the ``prepare_batch_scalar`` oracle pattern from the prep-vectorization
  round, applied to the codec).
- Engine batch feed: ``submit_many`` lands a whole bundle in ONE flush.
- ``Handlers.preverify_requests``: the batch verification seed shares
  the per-message memo discipline and fails item-wise.
- The bundle-ingest cluster path commits end-to-end.
- ``_ConcurrentStreamProcessor.cancel`` iterates a snapshot (a task
  finishing during cancel mutates the set via its done-callback).
"""

import asyncio
import os
import random
import sys

import pytest

from minbft_tpu import api
from minbft_tpu.messages import (
    Checkpoint,
    Hello,
    Prepare,
    Reply,
    Request,
    authen_bytes,
    marshal,
    unmarshal,
    unmarshal_batch,
)
from minbft_tpu.messages import codec as codec_mod
from minbft_tpu.messages.codec import CodecError

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from conftest import ledgers_reach, make_cluster  # noqa: E402


def _clear_intern():
    codec_mod._intern.clear()
    codec_mod._intern_bytes = 0


def _random_messages(rng, n):
    """Well-formed messages across kinds, REQUEST-heavy (the hot path)."""
    msgs = []
    for k in range(n):
        pick = rng.random()
        if pick < 0.55:
            msgs.append(
                Request(
                    client_id=rng.randrange(2**32),
                    seq=rng.randrange(2**64),
                    operation=rng.randbytes(rng.randrange(0, 96)),
                    signature=rng.randbytes(rng.randrange(0, 96)),
                    read_mode=rng.randrange(3),
                )
            )
        elif pick < 0.7:
            msgs.append(
                Reply(
                    replica_id=rng.randrange(2**32),
                    client_id=rng.randrange(2**32),
                    seq=rng.randrange(2**64),
                    result=rng.randbytes(rng.randrange(0, 64)),
                    signature=rng.randbytes(rng.randrange(0, 64)),
                    read_only=bool(rng.getrandbits(1)),
                    error=bool(rng.getrandbits(1)),
                )
            )
        elif pick < 0.8:
            msgs.append(
                Hello(
                    replica_id=rng.randrange(2**32),
                    resume_counter=rng.randrange(2**64),
                    signature=rng.randbytes(rng.randrange(0, 64)),
                )
            )
        elif pick < 0.9:
            msgs.append(
                Prepare(
                    replica_id=rng.randrange(2**32),
                    view=rng.randrange(2**32),
                    requests=tuple(
                        Request(
                            client_id=rng.randrange(2**32),
                            seq=rng.randrange(2**32),
                            operation=rng.randbytes(rng.randrange(0, 24)),
                            signature=rng.randbytes(8),
                        )
                        for _ in range(rng.randrange(1, 4))
                    ),
                )
            )
        else:
            msgs.append(
                Checkpoint(
                    replica_id=rng.randrange(2**32),
                    count=rng.randrange(2**32),
                    digest=rng.randbytes(32),
                    view=rng.randrange(2**16),
                    cv=rng.randrange(2**32),
                    bounds=((rng.randrange(4), rng.randrange(2**16)),),
                    signature=rng.randbytes(64),
                )
            )
    return msgs


def _corrupt(rng, frame: bytes) -> bytes:
    b = bytearray(frame)
    mode = rng.randrange(5)
    if mode == 0 and b:  # bit flip anywhere (tag, lengths, payload)
        b[rng.randrange(len(b))] ^= 1 << rng.randrange(8)
        return bytes(b)
    if mode == 1:  # truncation
        return bytes(b[: rng.randrange(len(b) + 1)])
    if mode == 2:  # trailing junk (must be rejected: one encoding per msg)
        return bytes(b) + rng.randbytes(rng.randrange(1, 8))
    if mode == 3:  # pure garbage
        return rng.randbytes(rng.randrange(0, 40))
    return b""  # empty frame


def test_unmarshal_batch_differential_fuzz():
    """1200+ frames through unmarshal_batch == item-by-item unmarshal:
    same accept/reject per frame, equal decoded messages, and a corrupt
    frame never affects its neighbours."""
    rng = random.Random(0xB16B00)
    frames = [marshal(m) for m in _random_messages(rng, 800)]
    frames += [_corrupt(rng, rng.choice(frames)) for _ in range(400)]
    rng.shuffle(frames)
    assert len(frames) >= 1200

    _clear_intern()
    got = unmarshal_batch(frames)
    _clear_intern()
    n_err = 0
    for fr, out in zip(frames, got):
        try:
            want = unmarshal(fr)
        except CodecError:
            want = None
        if want is None:
            n_err += 1
            assert isinstance(out, CodecError), (fr[:32], out)
        else:
            assert not isinstance(out, CodecError), (fr[:32], out)
            assert out == want
    # the corruption really exercised the reject path
    assert n_err >= 100


def test_unmarshal_batch_small_bundles_use_scalar_path():
    """Below the numpy threshold the contract is identical (item-wise
    values, errors as values)."""
    good = marshal(Request(client_id=1, seq=2, operation=b"x"))
    bad = good[:-1]
    out = unmarshal_batch([good, bad])
    assert isinstance(out[0], Request) and out[0].seq == 2
    assert isinstance(out[1], CodecError)


def test_unmarshal_batch_corrupt_frames_fail_item_wise():
    """A bundle mixing valid and corrupt REQUEST frames decodes every
    valid frame (large enough to take the vectorized path)."""
    rng = random.Random(7)
    reqs = [
        Request(client_id=i, seq=i * 7, operation=b"op-%d" % i,
                signature=b"s" * (i % 11))
        for i in range(64)
    ]
    frames = [marshal(r) for r in reqs]
    # corrupt every 4th frame
    for i in range(0, len(frames), 4):
        frames[i] = _corrupt(rng, frames[i])
    _clear_intern()
    out = unmarshal_batch(frames)
    for i, (r, got) in enumerate(zip(reqs, out)):
        if i % 4 == 0:
            continue  # may or may not decode (corruption is random)
        assert got == r, i


def test_unmarshal_batch_interns_requests():
    """Identical REQUEST wire bytes collapse to ONE object (the same
    dedup the scalar decoder provides for the n-replica fan-in)."""
    fr = marshal(Request(client_id=9, seq=9, operation=b"same"))
    _clear_intern()
    out = unmarshal_batch([fr] * 16)
    assert all(m is out[0] for m in out)
    # and a scalar decode of the same bytes hits the shared intern
    assert unmarshal(fr) is out[0]


def test_engine_submit_many_is_one_flush():
    """A bundle fed through verify_*_many lands as ONE engine batch
    (mean batch == bundle size), with per-item verdicts in order."""
    from minbft_tpu.parallel import BatchVerifier
    from minbft_tpu.utils import hostcrypto as hc
    import hashlib

    async def run():
        eng = BatchVerifier(max_batch=64, buckets=(64,))
        priv, pub = hc.keygen()
        items = []
        want = []
        for i in range(24):
            digest = hashlib.sha256(b"m%d" % i).digest()
            sig = hc.ecdsa_sign(priv, digest)
            if i % 5 == 0:  # corrupt some signatures: item-wise False
                sig = (sig[0], sig[1] ^ 1)
            items.append((pub, digest, sig))
            want.append(i % 5 != 0)
        got = await eng.verify_ecdsa_p256_host_many(items)
        assert got == want
        st = eng.stats["ecdsa_p256_host"]
        assert st.items == 24
        assert st.batches == 1, (st.batches, st.items)
        return True

    assert asyncio.run(run())


def test_preverify_seeds_one_engine_batch_and_coalesces():
    """Handlers.preverify_requests: a decoded bundle's outstanding
    signature checks reach the engine as ONE batch; the per-message
    validations that follow coalesce onto the seeded lanes (no second
    dispatch); failures stay item-wise on the per-message path; a
    revisit of validated requests seeds nothing."""
    from minbft_tpu.parallel import BatchVerifier

    async def run():
        engine = BatchVerifier(max_batch=64, buckets=(64,))
        replicas, c_auths, stubs, ledgers = await make_cluster(
            4, 1, n_clients=2, engines=[engine] * 4, batch_signatures=False
        )
        try:
            h = replicas[0].handlers
            assert h.authenticator.supports_batch_verify

            def signed(cid, seq, op):
                r = Request(client_id=cid, seq=seq, operation=op)
                r.signature = c_auths[cid].generate_message_authen_tag(
                    api.AuthenticationRole.CLIENT, authen_bytes(r)
                )
                return r

            good = [signed(i % 2, i, b"op%d" % i) for i in range(8)]
            bad = signed(0, 99, b"evil")
            bad.signature = b"\x00" * len(bad.signature)
            msgs = good[:4] + [bad] + good[4:]
            # cluster start-up (HELLO verification) may already have used
            # this queue: assert on DELTAS, not absolutes
            st0 = engine.stats.get("ecdsa_p256_host")
            items0 = st0.items if st0 else 0
            batches0 = st0.batches if st0 else 0
            assert h.preverify_requests(msgs) == len(msgs)
            # let the fire-and-forget seed land and resolve
            for t in list(h._bg_tasks):
                await t
            st = engine.stats["ecdsa_p256_host"]
            assert st.items - items0 == len(msgs)
            assert st.batches - batches0 == 1, (st.batches, st.items)
            # per-message validation: coalesces (memo/in-flight), no new
            # device items; the bad signature fails ONLY its request
            for m in good:
                await h.validate_message(m)
                assert h._marked(m, "_validated_by")
            with pytest.raises(api.AuthenticationError):
                await h.validate_message(bad)
            assert not h._marked(bad, "_validated_by")
            st = engine.stats["ecdsa_p256_host"]
            assert st.items - items0 == len(msgs), "per-message path re-dispatched"
            # already-validated requests seed nothing
            assert h.preverify_requests(good) == 0
            return True
        finally:
            for r in replicas:
                await r.stop()

    assert asyncio.run(run())


def test_cluster_commits_on_the_bundle_ingest_path():
    """End-to-end: a small cluster commits through bundle ingest, and
    the ingest tick metrics appear."""

    async def run():
        from minbft_tpu.client import new_client
        from minbft_tpu.sample.conn.inprocess import InProcessClientConnector

        replicas, c_auths, stubs, ledgers = await make_cluster(4, 1)
        client = new_client(
            0, 4, 1, c_auths[0], InProcessClientConnector(stubs), seq_start=0
        )
        await client.start()
        try:
            for i in range(5):
                await asyncio.wait_for(client.request(b"tick-%d" % i), 30)
            ticks = sum(
                r.metrics.counters.get("ingest_ticks", 0) for r in replicas
            )
            assert ticks > 0
            frames = sum(
                r.metrics.counters.get("ingest_frames", 0) for r in replicas
            )
            assert frames >= ticks
            await ledgers_reach(ledgers, 5)
            return True
        finally:
            await client.stop()
            for r in replicas:
                await r.stop()

    assert asyncio.run(run())


def test_stream_processor_cancel_iterates_snapshot():
    """cancel() must tolerate a task finishing DURING the cancel sweep:
    its done-callback discards it from the live set mid-iteration."""
    from minbft_tpu.core.message_handling import _ConcurrentStreamProcessor

    proc = _ConcurrentStreamProcessor(None, None)

    class FinishingTask:
        def __init__(self, tasks):
            self._tasks = tasks

        def cancel(self):
            # what add_done_callback(self._tasks.discard) does when the
            # task was already completing: the set shrinks under cancel()
            self._tasks.discard(self)

    proc._tasks.update({FinishingTask(proc._tasks) for _ in range(8)})
    proc.cancel()  # must not raise "Set changed size during iteration"
    assert not proc._tasks


def test_uvloop_knob_tri_state(monkeypatch):
    from minbft_tpu.utils.loop import maybe_enable_uvloop, uvloop_requested

    monkeypatch.setenv("MINBFT_UVLOOP", "0")
    assert uvloop_requested() is False
    assert maybe_enable_uvloop() is False
    monkeypatch.setenv("MINBFT_UVLOOP", "auto")
    assert uvloop_requested() is None
    monkeypatch.setenv("MINBFT_UVLOOP", "1")
    assert uvloop_requested() is True
    # uvloop may or may not be installed: the call must never raise, and
    # must only report True when the policy really switched.
    got = maybe_enable_uvloop()
    try:
        import uvloop  # noqa: F401,DC401 (availability probe)

        assert got is True
        import asyncio as aio

        aio.set_event_loop_policy(None)  # restore for later tests
    except ImportError:
        assert got is False
