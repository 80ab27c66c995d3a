"""Device-utilization ledger tests (minbft_tpu/obs/ledger.py, ISSUE 14):
the factor-product identity pinned to fp tolerance, the lane-class sum
invariant, baseline/window semantics against a synthetic engine with
hand-computed numbers, the self-ceiling fallback, and a live
BatchVerifier pass to keep the synthetic stats shape honest."""

import asyncio
import time

import pytest

from minbft_tpu.obs.ledger import DeviceLedger


class _Stats:
    """Mutable stand-in for VerifyStats/SignStats: only the fields the
    ledger reads, so a field rename there breaks here loudly."""

    def __init__(self, items=0, batches=0, padded_lanes=0, memo_hits=0,
                 host_fallback_items=0, device_time_s=0.0):
        self.items = items
        self.batches = batches
        self.padded_lanes = padded_lanes
        self.memo_hits = memo_hits
        self.host_fallback_items = host_fallback_items
        self.device_time_s = device_time_s


class _Engine:
    def __init__(self):
        self.stats = {}
        self.sign_stats = {}


def _mk(verify=None, sign=None):
    eng = _Engine()
    for name, st in (verify or {}).items():
        eng.stats[name] = st
    for name, st in (sign or {}).items():
        eng.sign_stats[name] = st
    return eng


# ---------------------------------------------------------------------------
# window accounting


def test_window_fields_from_hand_computed_deltas():
    eng = _mk(verify={"hmac_sha256": _Stats(
        items=10, batches=2, padded_lanes=6, memo_hits=3,
        device_time_s=1.0,
    )})
    led = DeviceLedger(eng, now=100.0)
    st = eng.stats["hmac_sha256"]
    st.items += 90
    st.batches += 8
    st.padded_lanes += 30
    st.memo_hits += 20
    st.device_time_s += 4.0
    wins = led.snapshot(now=110.0)
    win = wins["verify:hmac_sha256"]
    assert win.wall_s == pytest.approx(10.0)
    assert win.busy_s == pytest.approx(4.0)
    assert win.idle_s == pytest.approx(6.0)
    assert win.useful_lanes == 90  # deltas, not totals: baseline excluded
    assert win.padded_lanes == 30
    assert win.memo_lanes == 20
    assert win.fallback_lanes == 0
    assert win.batches == 8
    assert win.dispatched_lanes == 120
    assert win.mean_batch == pytest.approx(90 / 8)


def test_lane_classes_sum_to_total_lanes():
    eng = _mk(
        verify={"v": _Stats(items=50, batches=5, padded_lanes=14,
                            memo_hits=9, device_time_s=0.5)},
        sign={"s": _Stats(items=40, batches=4, padded_lanes=8,
                          host_fallback_items=12, device_time_s=0.25)},
    )
    led = DeviceLedger(eng, now=0.0)
    eng.stats["v"].items += 100
    eng.stats["v"].padded_lanes += 28
    eng.stats["v"].memo_hits += 7
    eng.stats["v"].batches += 4
    eng.stats["v"].device_time_s += 1.0
    eng.sign_stats["s"].items += 60
    eng.sign_stats["s"].padded_lanes += 4
    eng.sign_stats["s"].host_fallback_items += 15
    eng.sign_stats["s"].batches += 3
    eng.sign_stats["s"].device_time_s += 0.5
    wins = led.snapshot(now=5.0)
    v = wins["verify:v"]
    assert (v.useful_lanes + v.padded_lanes + v.memo_lanes
            + v.fallback_lanes) == v.total_lanes == 100 + 28 + 7
    s = wins["sign:s"]
    # sign items count every accepted item; host-fallback lanes never
    # crossed the device, so useful excludes them
    assert s.useful_lanes == 60 - 15
    assert s.fallback_lanes == 15
    assert (s.useful_lanes + s.padded_lanes + s.memo_lanes
            + s.fallback_lanes) == s.total_lanes == 60 + 4


def test_busy_is_clamped_to_wall_but_raw_overlap_kept():
    """max_inflight overlap can stack dispatch spans past the clock; the
    busy fraction must stay <= 1 while the raw sum stays readable."""
    eng = _mk(verify={"v": _Stats()})
    led = DeviceLedger(eng, now=0.0)
    st = eng.stats["v"]
    st.items, st.batches, st.device_time_s = 64, 2, 7.5
    win = led.snapshot(now=5.0)["verify:v"]
    assert win.busy_s == pytest.approx(5.0)
    assert win.device_time_s == pytest.approx(7.5)
    assert win.idle_s == 0.0
    dec = led.decompose(win, ceiling=100.0, source="test")
    assert dec.busy_fraction <= 1.0


def test_idle_queues_are_skipped():
    eng = _mk(verify={"v": _Stats(items=5, batches=1, device_time_s=0.1),
                      "w": _Stats()})
    led = DeviceLedger(eng, now=0.0)
    assert led.snapshot(now=1.0) == {}  # no movement anywhere
    eng.stats["v"].items += 1
    eng.stats["v"].batches += 1
    wins = led.snapshot(now=2.0)
    assert set(wins) == {"verify:v"}  # "w" never moved


# ---------------------------------------------------------------------------
# the headroom identity


def test_factor_product_equals_effective_rate():
    """effective = ceiling x busy x fill x useful, EXACTLY (fp): the
    factors are defined so the identity telescopes, and this test is the
    tripwire against a future clamp breaking it."""
    eng = _mk(verify={"v": _Stats()})
    led = DeviceLedger(eng, now=0.0)
    st = eng.stats["v"]
    st.items, st.batches = 900, 30
    st.padded_lanes, st.memo_hits = 120, 55
    st.device_time_s = 3.2
    win = led.snapshot(now=12.0)["verify:v"]
    for ceiling in (500.0, 10_000.0, 123_456.0):
        dec = led.decompose(win, ceiling=ceiling, source="test")
        assert dec.product() == pytest.approx(
            dec.effective_per_sec, rel=1e-9
        )
        assert dec.effective_per_sec == pytest.approx(900 / 12.0)
    # fill may exceed 1.0 when the live run beats a noisy probe ceiling:
    # the identity holds BECAUSE it is unclamped
    dec_low = led.decompose(win, ceiling=10.0, source="test")
    assert dec_low.fill_efficiency > 1.0
    assert dec_low.product() == pytest.approx(dec_low.effective_per_sec)


def test_self_ceiling_fallback_reads_fill_one():
    """With no calibrated ceiling the window's own busy lane rate is the
    ceiling (source 'self'): fill == 1.0 by construction and the
    identity still holds."""
    eng = _mk(verify={"v": _Stats()})
    led = DeviceLedger(eng, now=0.0)
    st = eng.stats["v"]
    st.items, st.batches, st.padded_lanes = 80, 10, 20
    st.device_time_s = 2.0
    win = led.snapshot(now=8.0)["verify:v"]
    dec = led.decompose(win)
    assert dec.ceiling_source == "self"
    assert dec.fill_efficiency == pytest.approx(1.0)
    assert dec.product() == pytest.approx(dec.effective_per_sec)


def test_set_ceiling_is_used_and_stamped():
    eng = _mk(verify={"hmac_sha256": _Stats()})
    led = DeviceLedger(eng, now=0.0)
    led.set_ceiling("hmac_sha256", 50_000.0, "probe:tpu")
    with pytest.raises(ValueError):
        led.set_ceiling("hmac_sha256", 0.0, "bad")
    st = eng.stats["hmac_sha256"]
    st.items, st.batches, st.device_time_s = 640, 10, 0.4
    keys = led.util_keys("e2e", "hmac_sha256", now=4.0)
    assert keys["e2e_util_ceiling_per_sec"] == 50_000.0
    assert keys["e2e_util_ceiling_source"] == "probe:tpu"


def test_util_keys_schema_and_absent_queue():
    eng = _mk(
        verify={"hmac_sha256": _Stats()},
        sign={"ecdsa_p256": _Stats()},
    )
    led = DeviceLedger(eng, now=0.0)
    st = eng.stats["hmac_sha256"]
    st.items, st.batches, st.padded_lanes = 100, 5, 28
    st.memo_hits, st.device_time_s = 4, 1.5
    keys = led.util_keys("cfg", "hmac_sha256", now=10.0)
    assert set(keys) == {
        "cfg_util_busy", "cfg_util_fill", "cfg_util_useful",
        "cfg_util_effective_per_sec", "cfg_util_per_device_per_sec",
        "cfg_util_ceiling_per_sec", "cfg_util_ceiling_source",
        "cfg_util_idle_s", "cfg_util_lanes_useful",
        "cfg_util_lanes_padding", "cfg_util_lanes_memo",
        "cfg_util_lanes_fallback",
    }
    assert keys["cfg_util_lanes_useful"] == 100
    assert keys["cfg_util_lanes_padding"] == 28
    assert keys["cfg_util_lanes_memo"] == 4
    # a queue this window never touched yields NO keys — honest absence,
    # not zeros (benchgate only gates keys present in both artifacts)
    assert led.util_keys("cfg", "never_ran", now=10.0) == {}
    # sign-side lookup works through the same entry point
    sg = eng.sign_stats["ecdsa_p256"]
    sg.items, sg.batches, sg.host_fallback_items = 30, 3, 30
    sg.device_time_s = 0.0
    skeys = led.util_keys("cfg", "ecdsa_p256", now=10.0)
    assert skeys["cfg_util_lanes_fallback"] == 30
    assert skeys["cfg_util_lanes_useful"] == 0


def test_probe_ceiling_times_a_full_bucket():
    calls = []

    def dispatch(batch):
        calls.append(len(batch))
        time.sleep(0.002)

    rate = DeviceLedger.probe_ceiling(dispatch, ("k", "m", "s"), 64)
    assert calls == [64]  # exactly one full-bucket dispatch
    assert 0 < rate < 64 / 0.002  # bounded by the sleep floor


def test_per_device_rate_uses_mesh_width():
    eng = _mk(verify={"v": _Stats()})
    eng._mesh = type("M", (), {"size": 4})()
    led = DeviceLedger(eng, now=0.0)
    assert led.n_devices == 4
    st = eng.stats["v"]
    st.items, st.batches, st.device_time_s = 400, 10, 1.0
    win = led.snapshot(now=10.0)["verify:v"]
    dec = led.decompose(win, ceiling=1000.0, source="test")
    assert dec.per_device_effective_per_sec == pytest.approx(
        dec.effective_per_sec / 4
    )


# ---------------------------------------------------------------------------
# live engine: the synthetic stats shape must match reality


def test_ledger_on_a_live_batch_verifier():
    """Run real HMAC verifies through a BatchVerifier and check every
    invariant on the measured window — if VerifyStats renames a field,
    the synthetic tests above would silently test a fiction; this one
    cannot."""
    import hashlib
    import hmac as hmac_mod

    from minbft_tpu.parallel import BatchVerifier

    async def run():
        eng = BatchVerifier(max_batch=8, buckets=(8,))
        key = b"\x01" * 32

        def item(i: int):
            msg = i.to_bytes(32, "big")  # fixed-width: the codec packs
            return key, msg, hmac_mod.new(key, msg, hashlib.sha256).digest()

        # warm (outside the window): the ledger baseline must absorb it
        assert all(await asyncio.gather(
            *[eng.verify_hmac_sha256(*item(i)) for i in range(8)]
        ))
        led = DeviceLedger(eng)
        warm_items = eng.stats["hmac_sha256"].items
        oks = await asyncio.gather(
            *[eng.verify_hmac_sha256(*item(100 + i))
              for i in range(5)]  # sub-bucket: padding appears
        )
        assert all(oks)
        win = led.snapshot()["verify:hmac_sha256"]
        assert win.useful_lanes == eng.stats["hmac_sha256"].items - warm_items
        assert win.useful_lanes == 5
        assert win.busy_s <= win.wall_s
        assert (win.useful_lanes + win.padded_lanes + win.memo_lanes
                + win.fallback_lanes) == win.total_lanes
        dec = led.decompose(win, ceiling=100_000.0, source="test")
        assert dec.product() == pytest.approx(dec.effective_per_sec)
        # high-water-mark satellite: peaks read-and-reset on the engine
        peaks = eng.queue_depth_peaks(reset=True)
        assert peaks.get("hmac_sha256", 0) >= 1
        assert eng.queue_depth_peaks(reset=True)["hmac_sha256"] == 0

    asyncio.run(run())
