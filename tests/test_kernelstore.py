"""utils/kernelstore.py through ops/lowering.py::per_mode_jit: a second
"process" (a fresh wrapper over the same function) loads the kernel's
executable and traces nothing; whatever the key names misses when it
changes; whatever goes wrong on the load path is counted, falls back to
building, and still answers.

CPU backend, the tests' bucket, the store handed in by argument in a
``tmp_path`` (the process's own store is None on this backend)."""

import asyncio
import functools
import hashlib
import hmac
import itertools
import os
import pickle
import stat
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minbft_tpu.obs import trace
from minbft_tpu.ops import ed25519 as ed
from minbft_tpu.ops import hmac_sha256 as hs
from minbft_tpu.ops import lowering, p256
from minbft_tpu.ops.lowering import per_mode_jit
from minbft_tpu.utils import hostcrypto as hc
from minbft_tpu.utils import jaxcache, kernelstore
from minbft_tpu.utils.kernelstore import KernelStore

BUCKET = 8
_ids = itertools.count()


@pytest.fixture
def store(tmp_path):
    return KernelStore(str(tmp_path / "cache" / kernelstore.SUBDIR))


@pytest.fixture
def fresh_compiles():
    """The compile cache off: XLA:CPU cannot serialize again an executable
    that it retrieved from the compile cache itself (the copy fails when it
    runs), so a kernel other tests have compiled is compiled afresh here."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


def anew(fn):
    """``fn`` under a new function object, so that no in-process cache of
    jit's (keyed by the function) hands back an executable that an earlier
    test's call retrieved from the compile cache."""

    @functools.wraps(fn)
    def kernel(*args):
        return fn(*args)

    del kernel.__wrapped__
    return kernel


def toy(name=None):
    """A kernel that traces in no time, under a name of its own (the
    counters are the process's, a row a kernel name)."""

    def kernel(a):
        return a.astype(jnp.uint32) * 3 + 1

    kernel.__name__ = kernel.__qualname__ = name or f"toy_{next(_ids)}"
    return kernel


def counters(fn) -> dict:
    return kernelstore.stats()[fn.__name__]


X = np.arange(BUCKET * 4, dtype=np.uint16).reshape(BUCKET, 4)
WANT = X.astype(np.uint32) * 3 + 1


# -- the three verify kernels: load, no trace, the traced kernel's verdicts --


def _ecdsa_case():
    items, want = [], []
    for i in range(5):
        d, q = hc.keygen()
        digest = hashlib.sha256(b"ks-%d" % i).digest()
        sig = hc.ecdsa_sign(d, digest)
        if i % 2:
            digest = hashlib.sha256(b"other-%d" % i).digest()
        items.append((q, digest, sig))
        want.append(i % 2 == 0)
    return p256._verify_one_packed, p256.prepare_packed(items, BUCKET), want


def _ed25519_case():
    items, want = [], []
    for i in range(5):
        seed, pub = hc.ed25519_keygen(bytes([i + 1]) * 32)
        msg = hashlib.sha256(b"ks-%d" % i).digest()
        sig = hc.ed25519_sign(seed, msg)
        if i % 2:
            msg = hashlib.sha256(b"other-%d" % i).digest()
        items.append((pub, msg, sig))
        want.append(i % 2 == 0)
    fn = ed.ed25519_verify_kernel_packed.__wrapped__
    return fn, ed.prepare_packed(items, BUCKET), want


def _hmac_case():
    rows, want = [], []
    for i in range(5):
        key = hashlib.sha256(b"key-%d" % i).digest()
        msg = hashlib.sha256(b"msg-%d" % i).digest()
        mac = hmac.new(key, msg, hashlib.sha256).digest()
        if i % 2:
            mac = bytes([mac[0] ^ 1]) + mac[1:]
        rows.append(key + msg + mac)
        want.append(i % 2 == 0)
    packed = np.zeros((BUCKET, 24), np.uint32)
    packed[:5] = np.frombuffer(b"".join(rows), dtype=">u4").reshape(5, 24)
    return hs.hmac_verify_kernel_packed.__wrapped__, packed, want


@pytest.mark.parametrize(
    "case", [_ecdsa_case, _ed25519_case, _hmac_case],
    ids=["ecdsa_verify", "ed25519_verify", "hmac_verify"],
)
def test_second_process_loads_without_tracing(case, store, fresh_compiles):
    jaxcache.record_jax_events()
    fn, packed, want = case()
    fn = anew(fn)
    name = fn.__name__
    before = kernelstore.stats().get(name, kernelstore.KernelStoreStats().to_dict())

    traced = np.asarray(per_mode_jit(fn, store=store)(jnp.asarray(packed)))
    assert [bool(v) for v in traced[:5]] == want
    built = counters(fn)
    assert built["builds"] == before["builds"] + 1
    assert built["loads"] == before["loads"]
    assert built["bytes"] > before["bytes"]
    (entry,) = os.listdir(store.directory)
    assert entry.startswith(f"{name}.loop.")

    rows_before = len(trace.timeline()["jax"]["rows"])
    loaded = np.asarray(per_mode_jit(fn, store=store)(jnp.asarray(packed)))
    events = [e for e, _t, _d in trace.timeline()["jax"]["rows"][rows_before:]]
    assert events == []  # no jaxpr_trace, no lowering, no compile
    assert loaded.dtype == traced.dtype and (loaded == traced).all()
    after = counters(fn)
    assert after["loads"] == before["loads"] + 1
    assert after["builds"] == built["builds"]
    assert after["load_failures"] == before["load_failures"]
    assert after["load_s"] >= after["read_s"] + after["deserialize_s"] > 0


def test_loaded_module_keeps_the_jit_name_the_benchmark_looks_for(store):
    fn = toy()
    per_mode_jit(fn, store=store)(X)
    again = KernelStore(store.directory)
    key = again.key(f"{fn.__module__}.{fn.__qualname__}", "loop",
                    ((X.shape, X.dtype),), jax.devices()[0])
    compiled, out = again.load(fn.__name__, key, jax.devices()[0])
    assert f"jit_{fn.__name__}" in compiled.as_text()[:200]
    assert out == [((BUCKET, 4), "uint32")]


# -- what the key names: each change misses ---------------------------------


def _change_digest(monkeypatch):
    monkeypatch.setattr(kernelstore, "_digest", "0" * 64)
    return X


def _change_mode(monkeypatch):
    monkeypatch.setattr(lowering, "_FORCE_MODE", "block")
    return X


def _change_shape(monkeypatch):
    return X[: BUCKET // 2]


def _change_device(monkeypatch):
    return jax.device_put(X, jax.devices()[1])


@pytest.mark.parametrize(
    "change", [_change_digest, _change_mode, _change_shape, _change_device],
    ids=["source_digest", "mode", "shape", "device"],
)
def test_a_changed_key_field_misses(change, store, monkeypatch):
    fn = toy()
    assert (np.asarray(per_mode_jit(fn, store=store)(X)) == WANT).all()
    assert (np.asarray(per_mode_jit(fn, store=store)(X)) == WANT).all()
    assert counters(fn)["loads"] == 1  # the same key hits ...
    x = change(monkeypatch)
    got = per_mode_jit(fn, store=store)(x)
    assert (np.asarray(got) == WANT[: x.shape[0]]).all()
    after = counters(fn)
    assert (after["loads"], after["builds"], after["load_failures"]) == (1, 2, 0)
    assert len(os.listdir(store.directory)) == 2  # ... the changed one is its own
    if change is _change_device:
        assert got.devices() == {jax.devices()[1]}


def test_one_wrapper_keeps_an_executable_a_device_and_a_shape(store):
    fn = toy()
    kernel = per_mode_jit(fn, store=store)
    on_1 = jax.device_put(X, jax.devices()[1])
    for _ in range(2):
        assert kernel(X).devices() == {jax.devices()[0]}
        assert kernel(on_1).devices() == {jax.devices()[1]}
        with jax.default_device(jax.devices()[2]):  # a pinned engine's scope
            assert kernel(X).devices() == {jax.devices()[2]}
        assert kernel(X[:2]).shape == (2, 4)
    assert counters(fn)["builds"] == 4 and counters(fn)["loads"] == 0


# -- the load path's faults: counted, built instead, rewritten --------------


def _truncate(store, path, other):
    with open(path, "rb") as fh:
        blob = fh.read()
    with open(path, "wb") as fh:
        fh.write(blob[: len(blob) // 2])


def _another_kernels_blob(store, path, other):
    with open(other, "rb") as fh:
        blob = fh.read()
    with open(path, "wb") as fh:
        fh.write(blob)


def _not_an_entry(store, path, other):
    with open(path, "wb") as fh:
        pickle.dump({"key": None}, fh)


def _group_writable_file(store, path, other):
    os.chmod(path, 0o660)


def _group_writable_directory(store, path, other):
    os.chmod(store.directory, 0o770)


@pytest.mark.parametrize(
    "fault",
    [_truncate, _another_kernels_blob, _not_an_entry, _group_writable_file,
     _group_writable_directory],
    ids=lambda f: f.__name__.lstrip("_"),
)
def test_a_fault_on_the_load_path_falls_back_and_is_counted(fault, store):
    fn, other = toy(), toy()
    per_mode_jit(other, store=store)(X[:2])
    per_mode_jit(fn, store=store)(X)
    path, other_path = (
        os.path.join(store.directory, next(
            n for n in os.listdir(store.directory) if n.startswith(f.__name__ + ".")))
        for f in (fn, other)
    )
    fault(store, path, other_path)
    got = np.asarray(per_mode_jit(fn, store=store)(X))
    assert (got == WANT).all()
    after = counters(fn)
    assert (after["loads"], after["builds"], after["load_failures"]) == (0, 2, 1)
    if fault is _group_writable_directory:
        # nothing is written into a directory others can write either
        assert after["save_failures"] == 1
        return
    # the entry was rewritten: the next process loads it
    assert stat.S_IMODE(os.stat(path).st_mode) == 0o600
    assert (np.asarray(per_mode_jit(fn, store=store)(X)) == WANT).all()
    assert counters(fn)["loads"] == 1


def test_a_wrong_result_shape_on_the_first_call_falls_back(store, monkeypatch):
    fn = toy()
    per_mode_jit(fn, store=store)(X)
    monkeypatch.setattr(kernelstore, "out_avals", lambda tree: [((1,), "bool")])
    assert (np.asarray(per_mode_jit(fn, store=store)(X)) == WANT).all()
    after = counters(fn)
    assert (after["loads"], after["builds"], after["load_failures"]) == (0, 2, 1)


def test_directory_and_entries_are_the_users_alone(store):
    per_mode_jit(toy(), store=store)(X)
    assert stat.S_IMODE(os.stat(store.directory).st_mode) == 0o700
    for name in os.listdir(store.directory):
        assert stat.S_IMODE(os.stat(os.path.join(store.directory, name)).st_mode) == 0o600
        assert not name.startswith(".tmp-")


def test_switched_off_writes_and_reads_nothing(store, monkeypatch):
    fn = toy()
    per_mode_jit(fn, store=store)(X)
    entries = os.listdir(store.directory)
    monkeypatch.setenv("MINBFT_JAX_CACHE", "0")
    kernel = per_mode_jit(fn, store=store)
    assert (np.asarray(kernel(X)) == WANT).all()
    assert (np.asarray(kernel(X[:2])) == WANT[:2]).all()
    assert os.listdir(store.directory) == entries
    after = counters(fn)
    assert (after["loads"], after["builds"], after["load_failures"]) == (0, 1, 0)


def test_first_calls_from_many_threads_build_once(store):
    fn = toy()
    kernel = per_mode_jit(fn, store=store)
    got = []
    threads = [
        threading.Thread(target=lambda: got.append(np.asarray(kernel(X))))
        for _ in range(6)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(got) == 6 and all((g == WANT).all() for g in got)
    assert counters(fn)["builds"] == 1


# -- what keeps today's path ------------------------------------------------


def test_cpu_backend_and_unplaced_calls_keep_the_plain_jit(store):
    assert jax.default_backend() == "cpu"
    assert kernelstore.default_store() is None
    fn = toy()
    assert (np.asarray(per_mode_jit(fn)(X)) == WANT).all()  # no store: no row
    assert fn.__name__ not in kernelstore.stats()
    # under another transformation the wrapper sees tracers
    kernel = per_mode_jit(fn, store=store)
    assert (np.asarray(jax.jit(kernel)(X)) == WANT).all()
    # an argument spread over devices is the jit's to place
    mesh = jax.make_mesh((2,), ("batch",))
    spread = jax.device_put(
        X, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("batch")))
    assert (np.asarray(kernel(spread)) == WANT).all()
    assert (np.asarray(per_mode_jit(fn, store=False)(X)) == WANT).all()
    assert fn.__name__ not in kernelstore.stats()
    assert not os.path.exists(store.directory)


def test_signature_reads_avals_and_device_without_tracing():
    devs = jax.devices()
    assert lowering._signature((X,)) == (((X.shape, X.dtype),), devs[0])
    on_3 = jax.device_put(X, devs[3])
    assert lowering._signature((X, on_3))[1] == devs[3]
    with jax.default_device(devs[2]):
        assert lowering._signature((jnp.asarray(X),))[1] == devs[2]
    assert lowering._signature((3,)) is None


def test_sources_digest_covers_what_the_trace_reads(tmp_path):
    root = tmp_path / "pkg"
    (root / "ops").mkdir(parents=True)
    (root / "utils").mkdir()
    (root / "ops" / "a.py").write_text("A = 1\n")
    (root / "ops" / "notes.txt").write_text("not read\n")
    (root / "utils" / "hostcrypto.py").write_text("P = 2\n")
    first = kernelstore.sources_digest(str(root))
    (root / "ops" / "notes.txt").write_text("still not read\n")
    assert kernelstore.sources_digest(str(root)) == first
    (root / "utils" / "hostcrypto.py").write_text("P = 3\n")
    second = kernelstore.sources_digest(str(root))
    (root / "ops" / "b.py").write_text("")
    assert len({first, second, kernelstore.sources_digest(str(root))}) == 3
    package = os.path.dirname(os.path.dirname(kernelstore.__file__))
    assert kernelstore.sources_digest() == kernelstore.sources_digest(package + "/")
    # the store is a sub-directory of the compile cache, and not an entry of it
    cache = tmp_path / "cache"
    (cache / kernelstore.SUBDIR).mkdir(parents=True)
    (cache / "jit_f-abc-cache").write_text("x")
    assert jaxcache.entry_count(str(cache)) == 1


# -- the names the engine and the benchmark's controls look up --------------


def test_the_benchmarks_skip_still_patches_the_entry_the_engine_calls():
    from benchmark.kernels import ecdsa_verify
    from minbft_tpu.parallel import BatchVerifier

    _d, q = hc.keygen()
    forged = (q, hashlib.sha256(b"skip").digest(), (7, 9))

    async def run():
        engine = BatchVerifier(max_batch=BUCKET, buckets=(BUCKET,))
        with ecdsa_verify.skip():
            skipped = await engine.verify_ecdsa_p256(*forged)
        # another forged item: the engine remembers a verdict it has given
        return skipped, await engine.verify_ecdsa_p256(q, forged[1], (9, 7))

    before = p256.ecdsa_verify_kernel_packed
    assert asyncio.run(run()) == (True, False)
    assert p256.ecdsa_verify_kernel_packed is before
    assert before.__wrapped__ is p256._verify_one_packed
    assert ecdsa_verify.TRACE_NAME == "jit_" + before.__name__


# -- the counter, where the tracing shows it --------------------------------


def test_counter_fields_in_timeline_engine_dump_and_prometheus(store):
    from minbft_tpu.obs import critpath, prom
    from minbft_tpu.parallel import BatchVerifier

    fn = toy("toy_counted")
    per_mode_jit(fn, store=store)(X)
    per_mode_jit(fn, store=store)(X)
    row = trace.timeline()["jax"]["kernel_store"]["toy_counted"]
    assert set(row) == {
        "loads", "builds", "off_main_loads", "off_main_builds", "load_failures",
        "save_failures", "load_s", "read_s", "deserialize_s", "digest_s",
        "build_s", "bytes",
    }
    assert (row["loads"], row["builds"], row["load_failures"]) == (1, 1, 0)
    assert row["bytes"] > 0 and row["build_s"] > 0
    assert kernelstore.totals()["loads"] >= 1

    async def run():
        eng = BatchVerifier(max_batch=BUCKET, buckets=(BUCKET,))
        doc = critpath.engine_queue_doc(eng, ident=3)
        return doc, prom.render_families(prom._collect_engine(eng, {"replica": "0"}))

    doc, text = asyncio.run(run())
    assert doc["kernel_store"]["toy_counted"] == row
    samples = prom.parse_exposition(text)
    for family in ("loads", "builds", "off_main_loads", "off_main_builds",
                   "load_failures", "load_seconds",
                   "read_seconds", "deserialize_seconds", "digest_seconds",
                   "build_seconds", "bytes"):
        assert f"minbft_kernel_store_{family}" in samples, family
    assert 'minbft_kernel_store_loads{kernel="toy_counted",replica="0"} 1' in text


# -- warm-up obtains on the calling thread; dispatchers find it there -------


class _Recorded:
    """An executable that notes the threads that call it."""

    def __init__(self, compiled):
        self.compiled = compiled
        self.threads = []

    def __call__(self, *args):
        self.threads.append(threading.current_thread())
        return self.compiled(*args)


class _RecordingStore:
    """A store that holds every key it is asked for (it compiles it there
    and then) and notes the thread that asked (the process's real store
    is None on this backend)."""

    def __init__(self):
        self.obtained = []  # (kernel name, key, thread)
        self.executables = []

    def obtain(self, fn, key, args, build=True):
        self.obtained.append((fn.__name__, key, threading.current_thread()))
        run = _Recorded(jax.jit(fn).lower(*args).compile())
        self.executables.append(run)
        return run, None


def test_warm_up_obtains_each_kernel_on_the_calling_thread(monkeypatch):
    from minbft_tpu.sample.peer import placement

    stub = _RecordingStore()
    # the module-level entries the dispatchers call, over the stub
    monkeypatch.setattr(p256, "ecdsa_verify_kernel_packed",
                        per_mode_jit(p256._verify_one_packed, store=stub))
    monkeypatch.setattr(p256, "per_mode_jit",
                        functools.partial(per_mode_jit, store=stub))
    monkeypatch.setattr(p256, "_kg_comb_batch", None)

    async def warm():
        engine, _line = placement.replica_engine(BUCKET, on_cpu=True)
        await placement.warm_engine(engine)
        return engine

    engine = asyncio.run(warm())
    here = threading.current_thread()
    assert sorted((name, key[1][0], thread) for name, key, thread in stub.obtained) == [
        ("_kg_comb_widen", ((BUCKET, p256.SIGN_COLS), np.dtype(np.uint16)), here),
        ("_verify_one_packed", ((BUCKET, p256.PACKED_COLS), np.dtype(np.uint16)), here),
    ]
    # each executable served its queue's one dispatch, on the dispatcher's
    # worker thread, and nothing was obtained there
    for run in stub.executables:
        assert len(run.threads) == 1 and run.threads[0] is not here
    verify, sign = engine.stats["ecdsa_p256"], engine.sign_stats["ecdsa_p256"]
    assert (verify.items, verify.batches, verify.dispatch_timeouts) == (1, 1, 0)
    assert (sign.items, sign.batches, sign.host_fallback_items) == (1, 1, 0)
    rows = engine.drain_obs_events()
    assert sorted(r[3] for r in rows) == ["sign", "verify"]
    assert all(r[7] == 0 for r in rows)  # no fallback, timeout or hostside row


def test_a_resolved_key_serves_worker_threads_without_a_second_obtain():
    stub = _RecordingStore()
    fn = toy()
    kernel = per_mode_jit(fn, store=stub)
    on_2 = jax.devices()[2]
    kernel.resolve(((BUCKET, 4), np.uint16))
    kernel.resolve(((BUCKET, 4), np.uint16))  # resolved already: nothing
    with jax.default_device(on_2):  # a pinned engine's scope: its own key
        kernel.resolve(((BUCKET, 4), np.uint16))
    assert [t for _n, _k, t in stub.obtained] == [threading.current_thread()] * 2
    assert [k[2] for _n, k, _t in stub.obtained] == [jax.devices()[0], on_2]

    def on_2_scope():
        with jax.default_device(on_2):
            return kernel(jnp.asarray(X))

    async def from_workers():
        return (await asyncio.to_thread(kernel, jnp.asarray(X)),
                await asyncio.to_thread(on_2_scope))

    got, got_2 = asyncio.run(from_workers())
    assert (np.asarray(got) == WANT).all() and (np.asarray(got_2) == WANT).all()
    assert got_2.devices() == {on_2}
    assert len(stub.obtained) == 2
    for run in stub.executables:
        assert len(run.threads) == 1
        assert run.threads[0] is not threading.current_thread()
    # no store: nothing obtained, nothing called
    per_mode_jit(fn, store=False).resolve(((BUCKET, 4), np.uint16))
    assert len(stub.obtained) == 2


def test_off_main_counters_count_what_a_worker_thread_loads_and_builds(
    store, fresh_compiles
):
    fn = toy()

    def on_a_worker(kernel, x):
        out = []
        worker = threading.Thread(target=lambda: out.append(np.asarray(kernel(x))))
        worker.start()
        worker.join(timeout=120)
        assert not worker.is_alive()
        return out[0]

    def split():
        row = counters(fn)
        return (row["loads"], row["off_main_loads"],
                row["builds"], row["off_main_builds"])

    # no entry yet: resolve leaves the key to its first call, which builds
    kernel = per_mode_jit(fn, store=store)
    kernel.resolve(((BUCKET, 4), np.uint16))
    assert split() == (0, 0, 0, 0) and not os.path.exists(store.directory)
    assert (on_a_worker(kernel, X) == WANT).all()
    assert split() == (0, 0, 1, 1)
    # a second process: loaded on the main thread, then called from a worker
    kernel = per_mode_jit(fn, store=store)
    kernel.resolve(((BUCKET, 4), np.uint16))
    assert split() == (1, 0, 1, 1)
    assert (on_a_worker(kernel, X) == WANT).all()
    assert split() == (1, 0, 1, 1)
    # a third, whose first call comes from a worker with nothing resolved
    assert (on_a_worker(per_mode_jit(fn, store=store), X) == WANT).all()
    assert split() == (2, 1, 1, 1)
    assert (np.asarray(per_mode_jit(fn, store=store)(X[:2])) == WANT[:2]).all()
    assert split() == (2, 1, 2, 1)  # a new key, built here


def test_without_a_store_warm_up_dispatches_as_it_did(monkeypatch):
    from minbft_tpu.sample.peer import placement

    assert kernelstore.default_store() is None
    store_rows = kernelstore.stats()

    def counts(engine):
        verify, sign = engine.stats["ecdsa_p256"], engine.sign_stats["ecdsa_p256"]
        return (
            [getattr(verify, f) for f in (
                "items", "batches", "max_batch_seen", "padded_lanes", "memo_hits",
                "dispatch_timeouts", "key_table_hits", "key_table_builds",
                "key_table_first_uses", "flush_reasons", "occupancy")],
            [getattr(sign, f) for f in (
                "items", "batches", "max_batch_seen", "padded_lanes",
                "dispatch_timeouts", "host_fallback_items", "flush_reasons",
                "occupancy")],
            sorted((r[2], r[3], r[4], r[5], r[6], r[7])
                   for r in engine.drain_obs_events()),
        )

    async def warm(load):
        engine, _line = placement.replica_engine(BUCKET, on_cpu=True)
        if not load:  # the warm-up as it was: no kernel obtained ahead
            monkeypatch.setattr(engine, "load_kernels", lambda schemes: None)
        await placement.warm_engine(engine)
        return counts(engine)

    now, before = asyncio.run(warm(True)), asyncio.run(warm(False))
    assert now == before
    assert now[0][:2] == [1, 1] and now[1][:2] == [1, 1]
    assert kernelstore.stats() == store_rows  # nothing met a store
