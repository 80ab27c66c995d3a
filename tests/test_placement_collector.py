"""The collector's policy of a serving replica
(`placement.settle_collector`): what warm-up leaves is frozen, the
thresholds are the stated constants, and the timeline says so.  Counts
only, never durations; `conftest.py` puts the collector back after each
case."""

import asyncio
import gc

import pytest

from minbft_tpu.obs import trace as obs_trace
from minbft_tpu.sample.peer import placement


def test_settle_collector_freezes_the_tracked_heap_and_sets_the_thresholds():
    kept = [[i] for i in range(1000)]  # tracked, alive: warm-up's stand-in
    cycle = []
    cycle.append(cycle)
    del cycle  # garbage: must be collected, not frozen
    gc.collect()
    tracked, frozen = len(gc.get_objects()), gc.get_freeze_count()
    placement.settle_collector()
    assert gc.get_threshold() == placement.COLLECTOR_THRESHOLDS
    assert gc.get_freeze_count() - frozen >= tracked - 100
    unfrozen = gc.get_objects()
    assert len(unfrozen) < 100  # this frame and little else
    assert not any(o is kept or o is kept[0] for o in unfrozen)


def test_settle_collector_twice_changes_nothing_but_the_frozen_count():
    placement.settle_collector()
    frozen = gc.get_freeze_count()
    since = [[k] for k in range(500)]  # the service's heap since then
    placement.settle_collector()
    assert gc.get_threshold() == placement.COLLECTOR_THRESHOLDS
    assert gc.get_freeze_count() >= frozen + len(since)
    assert gc.isenabled()


def test_timeline_carries_the_frozen_count_and_the_thresholds():
    before = obs_trace.timeline()["gc"]
    assert before["thresholds"] == list(gc.get_threshold())
    placement.settle_collector()
    got = obs_trace.timeline()["gc"]
    assert got["thresholds"] == list(placement.COLLECTOR_THRESHOLDS)
    assert got["frozen"] == gc.get_freeze_count() > before["frozen"]
    assert set(got) == {"rows", "dropped", "frozen", "thresholds"}


def test_warm_engines_settles_the_collector_even_with_no_engine():
    asyncio.run(placement.warm_engines([]))
    assert gc.get_threshold() == placement.COLLECTOR_THRESHOLDS


def test_policy_is_in_force_when_the_first_replica_starts(monkeypatch):
    """Warm-up, the collector settled, then `start()`: the one full pass
    the policy costs is off every protocol timer."""
    from minbft_tpu import core
    from minbft_tpu.sample.authentication import generate_testnet_keys
    from minbft_tpu.sample.config import SimpleConfiger

    seen = []
    real = core.new_replica

    def watched(*args, **kwargs):
        replica = real(*args, **kwargs)
        start = replica.start

        async def start_watched():
            seen.append((gc.get_threshold(), gc.get_freeze_count(),
                         len(gc.get_objects())))
            await start()

        replica.start = start_watched
        return replica

    monkeypatch.setattr(core, "new_replica", watched)

    async def drive():
        store = generate_testnet_keys(3, n_clients=1)
        cfg = SimpleConfiger(n=3, f=1, timeout_request=30.0, timeout_prepare=15.0)
        gc.collect()
        tracked = len(gc.get_objects())
        cluster = await placement.start_local_cluster(store, cfg, no_batch=True)
        await cluster.stop()
        return tracked

    tracked = asyncio.run(drive())
    assert len(seen) == 3
    thresholds, frozen, unfrozen = seen[0]
    assert thresholds == placement.COLLECTOR_THRESHOLDS
    assert frozen >= tracked - 100  # the keys, the modules, the replicas built
    assert unfrozen < 1000 < tracked


@pytest.mark.parametrize("replicas, youngest", [(0, 50_000), (1, 50_000), (3, 50_000),
                                                (7, 98_000), (31, 434_000)])
def test_first_threshold_grows_with_the_replicas_a_process_carries(replicas, youngest):
    """Twice what the replicas hold in flight, never under the floor; the
    middle and oldest thresholds are the constants at any size."""
    assert placement.collector_thresholds(replicas) == (youngest, 2, 10)
    placement.settle_collector(replicas)
    assert gc.get_threshold() == (youngest, 2, 10)


def test_warm_engines_settles_the_collector_for_the_replicas_it_is_told():
    asyncio.run(placement.warm_engines([], replicas=31))
    assert gc.get_threshold() == placement.collector_thresholds(31)
    assert obs_trace.timeline()["gc"]["thresholds"] == [434_000, 2, 10]


@pytest.mark.parametrize("scheme, usig_spec, points", [
    ("ecdsa-p256", "SOFT_ECDSA", 3 + 2 + 3),  # replicas, clients, USIG anchors
    ("ecdsa-p256", "HMAC_SHA256", 3 + 2),
    ("ed25519", "SOFT_ECDSA", 3),
    ("ed25519", "HMAC_SHA256", 0),
])
def test_prime_key_tables_builds_every_p256_key_the_store_names(scheme, usig_spec, points):
    """Only the store's keys can reach an engine's ECDSA queue, and their
    comb tables are built before a replica serves: after priming, every
    one of them is a hit."""
    from minbft_tpu.ops import p256
    from minbft_tpu.sample.authentication import generate_testnet_keys

    store = generate_testnet_keys(3, n_clients=2, scheme=scheme, usig_spec=usig_spec)
    keys = store.ecdsa_p256_points()
    assert len(keys) == len(set(keys)) == points
    assert all(p256.is_on_curve(*k) for k in keys)
    p256._KEY_TABLES.clear()
    placement.prime_key_tables(store)
    assert len(p256._KEY_TABLES) == points
    assert p256.prime_key_tables(keys).builds == 0
