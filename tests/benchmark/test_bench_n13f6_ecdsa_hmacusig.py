"""The deployment ``n13f6-ecdsa-hmacusig`` (ECDSA-P256 messages over
HMAC-SHA256 USIG certificates, 128-lane engines) and the serial cell
``n7f3-ecdsa.closed-1x1``: the real files as the manifest loads them, the
three kernels' work at 128 lanes against counts written out by hand, one
engine with both queues live against plain references lane by lane (more
than a bucket's worth at once, so that ``full`` flushes and a cut backlog
happen), the reader of ``engine.full_flush_share`` on hand-made ring rows,
and the real n=13 files through one untraced rehearsal window on the CPU
backend.  No window here is traced: nothing calibrates a lone dispatch on
the host's clock."""

import asyncio
import hashlib
import hmac
import itertools
import json
import os
import random
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import manifest, observe, roofline, run, spans, tracing  # noqa: E402
from benchmark.generator import Mix  # noqa: E402
from test_bench_deployments import CPU, drive  # noqa: E402  (this directory)
from test_bench_manifest import hold_the_schemes, per_layer_of  # noqa: E402  (this directory)
from test_bench_spans import T0, dispatch, observations, timeline  # noqa: E402

CELL = "n13f6-ecdsa-hmacusig.closed-16x8"
SERIAL = "n7f3-ecdsa.closed-1x1"
ACCEPTED = ["n7f3-ecdsa.closed-16x8", "n3f1-ecdsa.closed-16x8", "n31f15-ed25519.closed-16x8"]
SEED = 2**31 + 2033
MANIFEST = manifest.load_manifest()
FIELD_MUL = 2 * (2 * 32 * 32)  # product + reduction of 32 x 32 8-bit limbs, 2 operations a MAC
SHA256_COMPRESSION = 64 * (7 + 5 + 5 + 4 + 5) + 48 * (3 + 5 + 5) + 8  # word operations, FIPS 180-4
# By hand: operations a lane, bytes a lane.
BY_HAND = {
    # s^-1 and the affine x by Fermat (255 squarings, 128 products) with 2 products each,
    # 256 doublings of 8, an addition of 11 for three bit pairs in four
    "ecdsa_verify": ((255 + 128 + 2 + 256 * 8 + 192 * 11 + 255 + 128 + 2) * FIELD_MUL, 64 + 32 + 64 + 1),
    # k*G: 256 doublings of 8, an addition of 11 for every second bit
    "ecdsa_sign": ((256 * 8 + 128 * 11) * FIELD_MUL, 32 + 64),
    # two key pads of 8 words, four compressions, 8 words compared; four 8-bit operations a word
    "hmac_verify": ((16 + 4 * SHA256_COMPRESSION + 8) * 4, 32 + 32 + 32 + 1),
}


# -- the real files ---------------------------------------------------------------


def test_the_deployments_files_load_as_the_issue_states_them():
    cell = manifest.load_cell(CELL)
    config, entry = cell.config, next(c for c in MANIFEST["configs"] if c["name"] == cell.config_name)
    assert (config["n"], config["f"], config["scheme"], config["usig"]) == (13, 6, "ecdsa-p256", "HMAC_SHA256")
    assert config["engine"] == {"per_replica": True, "max_batch": 128, "buckets": [128]}
    assert config["kernels"] == ["ecdsa_verify", "ecdsa_sign", "hmac_verify"]
    assert (config["timeout_request"], config["timeout_prepare"]) == (60.0, 30.0)
    assert (config["connector"], config["groups"], config["chips"], config["hosts"]) == ("inprocess", 1, 1, 1)
    assert set(entry["reduced"]) == set(config["reduced"]) == {"hosts", "stream"}
    assert {"timeout_request", "state_machine", "keys"} <= set(config["assumed"])
    assert set(config["guarantees"]) == {"reply_quorum", "durability", "agreement", "device_path"}
    assert "configs[3]" in entry["source"] and "configs[3]" in config["source"]
    assert manifest.device_queues(manifest.load_kernels(cell)) == ["ecdsa_p256", "hmac_sha256"]
    assert cell.traffic_name == "closed-16x8" and cell.chips == 1
    mix = Mix.from_file(cell.traffic)
    assert (mix.loop, mix.clients, mix.depth, mix.payload_bytes) == ("closed", 16, 8, 35)
    assert callable(manifest.load_verifier(cell).make)


def test_the_serial_cell_is_n7f3_unedited_under_one_caller_with_one_write_in_flight():
    cell, flagship = manifest.load_cell(SERIAL), manifest.load_cell(ACCEPTED[0])
    assert cell.config == flagship.config and cell.config_name == "n7f3-ecdsa" and cell.chips == 1
    mix = Mix.from_file(cell.traffic)
    assert dict(vars(mix)) == {
        "loop": "closed", "clients": 1, "depth": 1, "rate_rps": None, "read_share": 0.0,
        "payload_bytes": 35, "forged_request_every": 64, "forged_reply_every": 64, "ack_wait_s": 60.0,
        "faults": ()}  # nothing fails in it
    # the rest of what test_bench_manifest.py asks of every cell (its 16 x 8 aside)
    assert {m["name"] for m in cell.end_to_end} == {
        "goodput_rps", "finality_mean_ms", "finality_p95_ms", "setup_s"}
    assert [m.name for m in cell.per_layer] == per_layer_of(MANIFEST, SERIAL)
    assert all(callable(m.read) for m in cell.per_layer)
    assert manifest.device_queues(manifest.load_kernels(cell)) == ["ecdsa_p256"]


@pytest.mark.parametrize("name", [CELL, SERIAL])
def test_each_new_cell_reports_every_entry_that_applies_and_no_other(name):
    """Every entry that applies by the manifest's one rule, whatever their
    number; the mixed cell the HMAC kernel's roofline and the USIG checks a
    commit, the ECDSA-only serial cell neither, and no Ed25519 metric."""
    cell = manifest.load_cell(name)
    names = [m.name for m in cell.per_layer]
    assert names == per_layer_of(MANIFEST, name)
    hold_the_schemes(cell)
    assert {"engine.full_flush_share", "kernel.ecdsa_verify_roofline", "kernel.ecdsa_sign_roofline",
            "protocol.device_items_per_commit", "device.idle_share"} <= set(names)
    mixed = {"kernel.hmac_verify_roofline", "protocol.usig_verifies_per_commit"}
    assert mixed & set(names) == (mixed if name == CELL else set())
    assert not any("ed25519" in n for n in names)


def test_the_ecdsa_cells_gain_the_one_new_metric_and_n31f15_stays_as_it_was():
    """``engine.full_flush_share`` (of the dispatches resolved in the window,
    those a whole bucket sent) reads a number in every cell, since every
    engine writes the dispatch ring, so it lists every cell, n31f15 among
    them; and n31f15 still reports no ECDSA metric."""
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == "engine.full_flush_share")
    seven = ACCEPTED + [CELL, SERIAL, "n7f3-ecdsa-2s1s.closed-16x8-primary-crash", "n7f3-ecdsa.open-0.8knee"]
    assert set(seven) <= set(entry["workloads"])
    for name in seven:
        assert "engine.full_flush_share" in per_layer_of(MANIFEST, name)
    n31f15 = manifest.load_cell(ACCEPTED[2])
    assert not any("ecdsa" in m.name for m in n31f15.per_layer)
    assert "ecdsa_p256" not in manifest.device_queues(manifest.load_kernels(n31f15))


# -- the kernels' work at 128 lanes -------------------------------------------------


@pytest.mark.parametrize("name", list(BY_HAND))
def test_kernels_work_at_128_lanes_is_the_textbooks_count(name):
    kernels = manifest.load_kernels(manifest.load_cell(CELL))
    ops, nbytes = BY_HAND[name]
    assert kernels[name].work(128) == {"ops": 128 * ops, "peak": "int8_ops_per_s", "bytes": 128 * nbytes}
    assert (FIELD_MUL, SHA256_COMPRESSION) == (4096, 2296)
    assert ops == {"ecdsa_verify": 4930 * 4096, "ecdsa_sign": 3456 * 4096, "hmac_verify": 9208 * 4}[name]
    # the share takes the run's lanes: the same kernel time at a quarter of
    # the lanes is a quarter of the share, whichever bound binds
    peaks = manifest.load_peaks("TPU v5 lite")
    _least, binds = roofline.least_time_s(kernels[name].work(128), peaks)
    assert binds == ("memory" if name == "hmac_verify" else "compute")
    shares = []
    for lanes in (128, 512):
        obs = observe.Observations(30.0, [], 0, [], "TPU v5 lite", "tpu", kernels, {name: 1e-3}, {}, lanes, None)
        shares.append(roofline.share_percent(obs, name))
    assert 0 < shares[0] < 100 and shares[1] == pytest.approx(4 * shares[0])


# -- one engine, both queues live, against the plain references -----------------------


def ecdsa_reference(q, digest: bytes, sig) -> bool:
    """OpenSSL's verdict through ``cryptography``: nothing of the program."""
    from cryptography.exceptions import InvalidSignature
    from cryptography.hazmat.primitives import hashes
    from cryptography.hazmat.primitives.asymmetric import ec, utils

    try:
        key = ec.EllipticCurvePublicNumbers(q[0], q[1], ec.SECP256R1()).public_key()
        key.verify(utils.encode_dss_signature(*sig), digest, ec.ECDSA(utils.Prehashed(hashes.SHA256())))
    except (InvalidSignature, ValueError):
        return False
    return True


def hmac_reference(key: bytes, msg: bytes, tag: bytes) -> bool:
    return hmac.compare_digest(hmac.new(key, msg, hashlib.sha256).digest(), tag)


def flip(data: bytes, rng: random.Random) -> bytes:
    i = rng.randrange(len(data))
    return data[:i] + bytes([data[i] ^ (1 << rng.randrange(8))]) + data[i + 1:]


def ecdsa_items(rng: random.Random, count: int) -> list:
    """``count`` distinct items under four keys, by turns: valid, a flipped
    digest, a flipped r or s, another key, a point off the curve, high s."""
    from minbft_tpu.utils import hostcrypto as hc

    keys = [hc.keygen(types.SimpleNamespace(randbelow=rng.randrange)) for _ in range(4)]
    out = []
    for i in range(count):
        d, q = keys[i % 4]
        digest = hashlib.sha256(b"n13f6 ecdsa %d %d" % (i, rng.getrandbits(32))).digest()
        r, s = hc.ecdsa_sign(d, digest)
        kind = i % 6
        if kind == 1:
            digest = flip(digest, rng)
        elif kind == 2:
            r, s = (r ^ (1 << rng.randrange(255)), s) if i % 4 < 2 else (r, s ^ (1 << rng.randrange(255)))
        elif kind == 3:
            q = keys[(i + 1) % 4][1]
        elif kind == 4:
            q = (q[0] ^ 1, q[1])
        elif kind == 5:
            s = hc.N - s
        out.append((q, digest, (r, s)))
    return out


def hmac_items(rng: random.Random, count: int) -> list:
    """``count`` distinct certificates, by turns: valid, a flipped key, a
    flipped message, a flipped tag."""
    out = []
    for i in range(count):
        key, msg = rng.randbytes(32), hashlib.sha256(b"n13f6 hmac %d" % i).digest()
        tag = hmac.new(key, msg, hashlib.sha256).digest()
        kind = i % 4
        key, msg, tag = (key, msg, tag) if kind == 0 else (
            (flip(key, rng), msg, tag) if kind == 1 else (
                (key, flip(msg, rng), tag) if kind == 2 else (key, msg, flip(tag, rng))))
        out.append((key, msg, tag))
    return out


async def through_one_engine(engine, ecdsa: list, certs: list) -> tuple:
    """Both queues at once.  First a burst of single submissions, ECDSA and
    HMAC by turns, more than two buckets' worth each before the loop turns;
    then a backlog of three buckets and a half handed over whole
    (``verify_ecdsa_p256_many``), which ``_flush_now`` has to cut, beside
    more certificates.  -> (ECDSA verdicts, HMAC verdicts), in input order."""
    half_e, half_h = len(ecdsa) // 2, len(certs) // 2
    turns = [item for pair in itertools.zip_longest(ecdsa[:half_e], certs[:half_h])
             for item in pair if item is not None]
    first = await asyncio.gather(*[
        (engine.verify_hmac_sha256 if is_cert(item) else engine.verify_ecdsa_p256)(*item)
        for item in turns])
    backlog, *rest = await asyncio.gather(
        engine.verify_ecdsa_p256_many(ecdsa[half_e:]),
        *[engine.verify_hmac_sha256(*h) for h in certs[half_h:]])
    first_e = [v for item, v in zip(turns, first) if not is_cert(item)]
    first_h = [v for item, v in zip(turns, first) if is_cert(item)]
    return first_e + list(backlog), first_h + list(rest)


def is_cert(item) -> bool:
    return isinstance(item[0], bytes)


def test_one_engine_with_both_queues_live_equals_the_plain_references_in_every_lane():
    from minbft_tpu.parallel import BatchVerifier

    rng = random.Random(SEED)
    bucket = 8
    ecdsa, certs = ecdsa_items(rng, 3 * bucket + 3 * bucket + 4), hmac_items(rng, 3 * bucket + 12)
    want_e = [ecdsa_reference(*item) for item in ecdsa]
    want_h = [hmac_reference(*item) for item in certs]
    # every kind of item is there, and on both sides of the verdict
    assert want_e == [i % 6 in (0, 5) for i in range(len(ecdsa))]  # valid, and high s
    assert want_h == [i % 4 == 0 for i in range(len(certs))]
    kernels = manifest.load_kernels(manifest.load_cell(CELL))

    async def sound():
        engine = BatchVerifier(max_batch=bucket, buckets=(bucket,))
        got = await through_one_engine(engine, ecdsa, certs)
        return got, engine.stats

    (got_e, got_h), stats = asyncio.run(sound())
    assert got_e == want_e
    assert got_h == want_h
    # each queue's items on its own side, no dispatch rescued on the host, none lost to the memo
    assert set(stats) == {"ecdsa_p256", "hmac_sha256"}
    ecdsa_stats, hmac_stats = stats["ecdsa_p256"], stats["hmac_sha256"]
    assert (ecdsa_stats.items, hmac_stats.items) == (len(ecdsa), len(certs))
    for st in (ecdsa_stats, hmac_stats):
        assert (st.dispatch_timeouts, st.memo_hits) == (0, 0)
        assert sum(st.flush_reasons.values()) == st.batches and st.max_batch_seen == bucket
        assert st.flush_reasons["full"] > 0, st.flush_reasons
    # the backlog of 3.5 buckets left as whole buckets, two at once at most
    assert ecdsa_stats.batches >= -(-len(ecdsa) // bucket)
    assert ecdsa_stats.padded_lanes + ecdsa_stats.items == ecdsa_stats.batches * bucket

    # ... and the comparison above is one that a kernel answering "valid" in
    # every lane fails, queue by queue (a fresh engine: no memo of the sound pass)
    async def skipped(name):
        engine = BatchVerifier(max_batch=bucket, buckets=(bucket,))
        with kernels[name].skip():
            return await through_one_engine(engine, ecdsa[:2 * bucket], certs[:2 * bucket])

    blind_e, sound_h = asyncio.run(skipped("ecdsa_verify"))
    assert all(blind_e) and blind_e != want_e[:2 * bucket] and sound_h == want_h[:2 * bucket]
    sound_e, blind_h = asyncio.run(skipped("hmac_verify"))
    assert all(blind_h) and blind_h != want_h[:2 * bucket] and sound_e == want_e[:2 * bucket]


# -- engine.full_flush_share on hand-made ring rows --------------------------------------


def full_flush_share(monkeypatch, rows, **kw):
    read = manifest.by_name(REPO, "layer_metrics", "engine.full_flush_share", "reader").read
    monkeypatch.setattr(spans, "timeline", lambda: timeline(rows=rows, **kw))
    return read(observations())


def row(k, at, reason, **kw):
    """A dispatch resolved ``at`` ms into the window that left its queue for ``reason``."""
    r = list(dispatch(k, at - 12, at - 11, at - 10, at, **kw))
    r[6] = reason
    return tuple(r)


def test_full_flush_share_is_the_full_rows_over_the_rows_resolved_in_the_window(monkeypatch):
    quiet = [row(1, 20, "idle"), row(2, 40, "completion"), row(3, 60, "completion", kind="sign")]
    assert full_flush_share(monkeypatch, quiet) == 0.0
    mixed = quiet + [row(4, 30, "full"), row(5, 70, "full", queue="hmac_sha256", engine=1)]
    assert full_flush_share(monkeypatch, mixed) == 2 / 5
    # rows resolved before the window opened or after it closed count on neither side
    outside = mixed + [row(6, -20, "full"), row(7, 130, "full")]
    assert outside[-1][-1] > T0 + 100_000_000 and full_flush_share(monkeypatch, outside) == 2 / 5
    assert full_flush_share(monkeypatch, [row(6, -20, "full")]) is None  # nothing to read
    # a ring that dropped rows written inside the window: no number, not a wrong one
    assert full_flush_share(monkeypatch, mixed, dropped={"dispatch": 3}) is None
    early = [row(0, -90, "idle"), row(8, -80, "full", engine=1)] + mixed
    assert full_flush_share(monkeypatch, early, dropped={"dispatch": 3}) == 2 / 5
    monkeypatch.setattr(spans, "timeline", lambda: None)  # a program without a timeline
    assert manifest.by_name(REPO, "layer_metrics", "engine.full_flush_share", "reader").read(
        observations()) is None


# -- the real n=13 files, one untraced rehearsal window -------------------------------------


def test_the_full_cluster_of_13_runs_one_untraced_window_correct_on_the_cpu_rehearsal():
    cell = manifest.load_cell(CELL)
    config, mix = run.sized(cell, CPU)
    assert (config["n"], config["f"], config["engine"]["buckets"], mix.clients) == (13, 6, [8], 2)

    async def untraced(system, mix):
        return await run.measured(cell, CPU, system, mix, SEED + 1, 2.0, False)

    async def engines(system, mix):
        return [(e.stats, e.sign_stats, e.written_off()) for e in system.engines]

    result, per_engine = drive(cell, [untraced, engines])
    assert result["correct"] is True and result["attempted"] > 0, json.dumps(result["compared"])
    assert len(result["compared"]) == 9 and not any(line["value"] for line in result["compared"].values())
    assert result["failed"] == 0 and result["workload"] == CELL
    assert set(result["metrics"]) == {"goodput_rps", "finality_mean_ms", "finality_p95_ms", "setup_s"}
    assert len(per_engine) == 13
    for verify, sign, written_off in per_engine:
        # ECDSA requests and reply signatures in one queue, certificates in the
        # other, made on the host: a verify-only queue
        assert set(verify) == {"ecdsa_p256", "hmac_sha256"} and set(sign) == {"ecdsa_p256"}
        assert verify["ecdsa_p256"].items > 0 and verify["hmac_sha256"].items > 0
        assert sign["ecdsa_p256"].items > 0 and sign["ecdsa_p256"].host_fallback_items == 0
        assert not written_off
        assert not any(st.dispatch_timeouts for st in list(verify.values()) + list(sign.values()))


# -- the calibration floor at 128 lanes, against the chip's recording ---------------------


def recorded_sessions() -> list:
    with open(os.path.join(manifest.HERE, "recorded", "calibration_128_lanes_pr33.json")) as fh:
        return json.load(fh)["sessions"]


@pytest.mark.parametrize("side,anchored,kept,made", [
    ("parent", False, 2, 9), ("change", False, 5, 7), ("parent", True, 9, 9), ("change", True, 7, 7)])
def test_recorded_128_lane_sessions_reduce_as_they_did_on_the_chip(side, anchored, kept, made):
    """What ``tracing.FLOOR`` alone said of the sessions PR 33 recorded: with
    the first-use ladder inside the verify dispatch the parent's two runs
    kept their fifth and their fourth session, the change's five runs their
    first (three) or their second (two): the HOST was slow, every verify
    event was whole.  Held to the kernel's own recorded time first
    (``benchmark/recorded/anchors/ecdsa_verify.128.json``, PR 35), every
    session is kept."""
    kernels = manifest.load_kernels(manifest.load_cell(CELL))
    anchors = tracing.anchors(kernels, 128) if anchored else None
    assert anchors is None or anchors == {"ecdsa_verify": pytest.approx(1.394e-3)}
    sessions = [s for s in recorded_sessions() if s["side"] == side]
    verdicts = []
    for s in sessions:
        summary = {"planes": [{"name": "/device:TPU:0",
                               "lines": [{"name": "XLA Modules", "events": s["events"]}]}]}
        runs = [(name, 0.0, seconds) for name, seconds in s["runs"]]
        try:
            times = tracing.reduce_calibration(summary, tracing.TPU_EVENTS, kernels, runs, anchors)
        except tracing.TraceError as e:
            assert "a lone event" in str(e) and "ecdsa_verify" in str(e)
            verdicts.append(False)
            continue
        assert 1.39e-3 < times["ecdsa_verify"] < 1.40e-3 and 1.40e-3 < times["ecdsa_sign"] < 1.41e-3
        assert 7.1e-6 < times["hmac_verify"] < 7.4e-6
        assert anchored or times["ecdsa_verify"] >= tracing.FLOOR * s["runs"][0][1]
        verdicts.append(True)
    assert sum(verdicts) == kept
    last_of_each_run = [v for s, v, nxt in zip(sessions, verdicts, sessions[1:] + [None])
                        if nxt is None or nxt["seed"] != s["seed"]]
    assert all(last_of_each_run)  # every recorded run ended on a session it could use
    assert len(verdicts) == made


def test_an_anchor_keeps_no_sliver_and_no_event_longer_than_its_dispatch():
    """The kernel's recorded time admits whole events only: one caught from
    its middle on is still held to the floor (and fails it on a slow host),
    and the cold run's 38.219 ms event, longer than its dispatch, is still
    none of the session's."""
    kernels = manifest.load_kernels(manifest.load_cell(CELL))
    anchors = tracing.anchors(kernels, 128)
    session = recorded_sessions()[0]
    runs = [(name, 0.0, seconds) for name, seconds in session["runs"]]

    def reduce(verify_ns: float, runs=runs):
        events = [[name, start, verify_ns if name.startswith("jit__verify") else ns]
                  for name, start, ns in session["events"]]
        summary = {"planes": [{"name": "/device:TPU:0",
                               "lines": [{"name": "XLA Modules", "events": events}]}]}
        return tracing.reduce_calibration(summary, tracing.TPU_EVENTS, kernels, runs, anchors)

    assert reduce(1394111.0)["ecdsa_verify"] == pytest.approx(1.394111e-3)
    with pytest.raises(tracing.TraceError, match="a lone event of 0.000697"):
        reduce(697000.0)
    with pytest.raises(tracing.TraceError, match="1 longer than a dispatch"):
        reduce(38219000.0)
    # a later PR's faster kernel disagrees with the anchor and is judged as before PR 35:
    # by the host's clock, 0.9 ms of this session's 8.2 ms dispatch, or of one of 4.4 ms
    with pytest.raises(tracing.TraceError, match="a lone event of 0.000900"):
        reduce(900000.0)
    assert reduce(900000.0, [(runs[0][0], 0.0, 0.0044)] + runs[1:])["ecdsa_verify"] == pytest.approx(9e-4)
