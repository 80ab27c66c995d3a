"""Deployments that BENCHMARK.json does not hold yet, taken as files alone:
a temporary checkout gets a configuration, the files of its kernels and its
cell (the later PR's part), and the harness (this checkout's code, unedited)
runs the cell on the CPU backend through the run's own window, comparison
and result line.  One with Ed25519 messages over ECDSA certificates (two
queues, the Ed25519 reference verifier decides), one with ECDSA messages
over HMAC certificates (two queues, three kernels, a verify-only queue)."""

import asyncio
import contextlib
import copy
import dataclasses
import json
import logging
import os
import shutil
import sys
import types

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import compare, controls, manifest, run, spans  # noqa: E402
from benchmark import system as sut  # noqa: E402
from bench_timeline import timeline_from_here  # noqa: E402  (this directory)

CPU = {"platform": "cpu", "kind": "cpu", "count": 1, "rehearsal": True}
SEED = 2**31 + 2028
MANIFEST = manifest.load_manifest()

# A kernel's file as a later PR writes it, less the textbook work (a
# rehearsal has no roofline): where its dispatches are counted, one dispatch
# through an engine, and for a verify kernel the control's skip().
KERNEL_FILE = '''
import contextlib, hashlib, hmac
TRACE_NAME = "{trace_name}"
QUEUE = "{queue}"
KIND = "{kind}"
CALIBRATION_RUNS = 1


def work(lanes):
    return {{"ops": lanes * {ops}, "peak": "int8_ops_per_s", "bytes": lanes * 128}}


async def dispatch_once(engine, salt):
    from minbft_tpu.utils import hostcrypto
    digest = hashlib.sha256(salt).digest()
    {dispatch}


@contextlib.contextmanager
def skip():
    import numpy as np
    from minbft_tpu.ops import {module} as ops
    kernel = ops.{entry}
    ops.{entry} = lambda packed: np.ones(packed.shape[0], bool)
    try:
        yield
    finally:
        ops.{entry} = kernel
'''
ED25519_VERIFY = KERNEL_FILE.format(
    trace_name="jit__ed25519_verify", queue="ed25519", kind="verify", ops=3000,
    module="ed25519", entry="ed25519_verify_kernel_packed",
    dispatch="seed, pub = hostcrypto.ed25519_keygen()\n"
             "    assert await engine.verify_ed25519(pub, digest, hostcrypto.ed25519_sign(seed, digest))")
ED25519_SIGN = KERNEL_FILE.format(
    trace_name="jit_widen", queue="ed25519", kind="sign", ops=1000,
    module="ed25519", entry="ed25519_rb_kernel",
    dispatch="await engine.sign_ed25519(hostcrypto.ed25519_keygen()[0], digest)")
HMAC_VERIFY = KERNEL_FILE.format(
    trace_name="jit_hmac_verify_kernel_packed", queue="hmac_sha256", kind="verify", ops=10,
    module="hmac_sha256", entry="hmac_verify_kernel_packed",
    dispatch="assert await engine.verify_hmac_sha256("
             "digest, digest, hmac.new(digest, digest, hashlib.sha256).digest())")


def later_checkout(root, name: str, scheme: str, usig: str, kernels: dict) -> manifest.Cell:
    """A copy of this checkout's benchmark/ under ``root`` with one more
    configuration, ``kernels`` = {name: None (a file the benchmark has) or
    the file's text}, and its cell under the accepted closed-loop mix; every
    per-layer metric that lists its cells admits the new one."""
    here = root / "benchmark"
    shutil.copytree(manifest.HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    config = json.loads((here / "configs" / "n3f1-ecdsa.json").read_text())
    config.update(name=name, scheme=scheme, usig=usig, kernels=list(kernels))
    (here / "configs" / f"{name}.json").write_text(json.dumps(config))
    for kernel, text in kernels.items():
        if text is not None:
            (here / "kernels" / f"{kernel}.py").write_text(text)
    later = json.loads(json.dumps(MANIFEST))
    cell = f"{name}.closed-16x8"
    later["configs"].append({"name": name, "source": "x", "why": "y", "reduced": ["hosts"],
                             "file": f"benchmark/configs/{name}.json"})
    later["workloads"].append({"name": cell, "config": name, "traffic": "closed-16x8",
                               "chips": 1, "why": "z"})
    for entry in later["per_layer"]:
        if "workloads" in entry:
            entry["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(later))
    return manifest.load_cell(cell, root=str(root))


def drive(cell, steps):
    """Build the cell's cluster at the rehearsal's size and run ``steps``
    over it, each ``async (system, mix) -> anything`` -> what each returned."""
    async def everything():
        config, mix = run.sized(cell, CPU)
        system = await sut.build(cell, config, mix.clients, on_cpu=True)
        try:
            return [await step(system, mix) for step in steps]
        finally:
            await system.stop()

    logging.disable(logging.WARNING)
    try:
        with timeline_from_here():
            return asyncio.run(everything())
    finally:
        logging.disable(logging.NOTSET)


# -- (a) Ed25519 messages, ECDSA certificates ---------------------------------


@pytest.fixture(scope="module")
def ed25519_over_ecdsa(tmp_path_factory):
    cell = later_checkout(
        tmp_path_factory.mktemp("ed25519"), "n3f1-ed25519", "ed25519", "SOFT_ECDSA",
        {"ed25519_verify": ED25519_VERIFY, "ed25519_sign": ED25519_SIGN, "ecdsa_verify": None})

    async def untraced(system, mix):
        return await run.measured(cell, CPU, system, mix, SEED, 1.0, False)

    async def controlled(system, mix):
        plan = [("replies_unverified", SEED + 1), ("sound", SEED + 2)]
        return await controls.windows(system, mix, plan, 1.0, lambda line: None)

    result, lines = drive(cell, [untraced, controlled])
    return cell, result, {ln["step"]: ln for ln in lines}


def test_an_ed25519_deployment_runs_correct_from_files_alone(ed25519_over_ecdsa):
    cell, result, lines = ed25519_over_ecdsa
    assert manifest.device_queues(manifest.load_kernels(cell)) == ["ed25519", "ecdsa_p256"]
    assert result["correct"] is True and result["attempted"] > 0, json.dumps(result["compared"])
    assert not any(line["value"] for line in result["compared"].values())
    assert result["notes"]["shadowed_writes"] > 0  # the reference verifier had forged replies to refuse
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert lines["sound"]["correct"] is True, lines["sound"]


def test_the_ed25519_reference_verifier_fails_clients_that_take_replies_on_trust(ed25519_over_ecdsa):
    line = ed25519_over_ecdsa[2]["replies_unverified"]
    assert line["correct"] is False
    assert line["numbers"]["wrong_results"] > 0 and line["numbers"]["acks_short_of_quorum"] > 0, line


def test_a_scheme_without_a_verifier_file_names_the_file_to_add(tmp_path):
    cell = later_checkout(tmp_path, "n3f1-p384", "ecdsa-p384", "SOFT_ECDSA",
                          {"ecdsa_verify": None, "ecdsa_sign": None})
    with pytest.raises(manifest.BenchmarkError, match=r"benchmark/verifiers/ecdsa-p384\.py"):
        manifest.load_verifier(cell)
    with pytest.raises(manifest.BenchmarkError, match=r"benchmark/kernels/p384_verify\.py"):
        manifest.load_kernels(dataclasses.replace(cell, config={"kernels": ["p384_verify"]}))


# -- (b), (c) ECDSA messages, HMAC certificates: two queues, three kernels ------


class StillQueue:
    """An engine as the harness reads it, with one verify queue's counters
    held at what they were: a queue that did no work."""

    def __init__(self, engine, queue: str):
        self._engine = engine
        self._still = {queue: copy.deepcopy(engine.stats[queue])}

    def __getattr__(self, name):
        return getattr(self._engine, name)

    @property
    def stats(self):
        return {**self._engine.stats, **self._still}


@pytest.fixture(scope="module")
def ecdsa_over_hmac(tmp_path_factory):
    cell = later_checkout(
        tmp_path_factory.mktemp("hmacusig"), "n3f1-ecdsa-hmacusig", "ecdsa-p256", "HMAC_SHA256",
        {"ecdsa_verify": None, "ecdsa_sign": None, "hmac_verify": HMAC_VERIFY})
    seen = {}

    async def traced(system, mix):
        readers = run.per_layer

        def per_layer(cell, obs):  # the Observations the readers got, for the cases to look at
            seen.update(obs=obs, rows=spans.dispatch_rows(spans.timeline(), spans.analysis(obs).opened))
            return readers(cell, obs)

        run.per_layer = per_layer
        try:
            return await run.measured(cell, CPU, system, mix, SEED + 3, 1.0, True)
        finally:
            run.per_layer = readers

    async def with_the_hmac_queue_still(system, mix):
        engines = system.cluster.engines
        system.cluster.engines = [StillQueue(e, "hmac_sha256") for e in engines]
        try:
            return await run.one_window(system, mix, SEED + 4, 1.0, tag=b"s")
        finally:
            system.cluster.engines = engines

    result, still = drive(cell, [traced, with_the_hmac_queue_still])
    return cell, result, seen, still


def test_a_two_queue_deployment_runs_correct_and_counts_each_kernel_in_its_own_queue(ecdsa_over_hmac):
    cell, result, seen, _ = ecdsa_over_hmac
    kernels = manifest.load_kernels(cell)
    assert manifest.device_queues(kernels) == ["ecdsa_p256", "hmac_sha256"]
    assert result["correct"] is True, json.dumps(result["compared"])
    dispatches = result["notes"]["kernel_dispatches"]
    assert set(dispatches) == set(kernels) and all(n > 0 for n in dispatches.values()), dispatches
    # the summed counters hold both verify queues, each kernel's dispatches only its own
    assert seen["obs"].total("verify_batches") == dispatches["ecdsa_verify"] + dispatches["hmac_verify"]
    assert seen["obs"].total("sign_batches") == dispatches["ecdsa_sign"]
    times = result["notes"]["trace_sessions"][-1]["kernel_time_s"]
    assert set(times) == set(kernels) and all(t > 0 for t in times.values())
    assert [name for name, _s in result["breakdown"]["device_ops"]] and len(
        result["breakdown"]["device_ops"]) == 3
    assert result["device"]["busy_s"] == pytest.approx(
        sum(dispatches[k] * times[k] for k in kernels))


def test_a_traced_two_queue_window_places_each_dispatch_at_its_own_kernels_length(ecdsa_over_hmac):
    cell, result, seen, _ = ecdsa_over_hmac
    obs, rows = seen["obs"], seen["rows"]
    kernel_ns = spans.kernel_ns_by_side(obs)
    assert set(kernel_ns) == {("ecdsa_p256", "verify"), ("ecdsa_p256", "sign"), ("hmac_sha256", "verify")}
    assert len(set(kernel_ns.values())) == 3
    # the live ring's names (hmac_sha256, ecdsa_p256, sign_ecdsa_p256) are the files' sides
    table = spans.device_intervals(rows, kernel_ns)
    assert {spans.side(r) for r, _s, _e in table} == set(kernel_ns)
    assert all(e - s == kernel_ns[spans.side(r)] for r, s, e in table)
    # five idle classes, every instant of the window in one of them or under a kernel
    a = spans.analysis(obs)
    idle = {c: result["metrics"][f"device.idle_{c}_share"]["value"] for c in spans.CLASSES}
    assert all(0 <= v <= 1 for v in idle.values())
    assert sum(idle.values()) == pytest.approx(1 - a.classes["busy"] / a.window_ns, abs=1e-9)
    # on the CPU the host's clock stands in for the kernels' times, and the
    # modelled device is saturated: device.idle_share (counters x time) is
    # reported, and is compared with the classes' sum on the chip only
    assert "device.idle_share" in result["metrics"]
    assert set(result["metrics"]) == {
        m.name for m in cell.per_layer if not (m.unit == "%" and m.source == "device_trace")}


def test_a_queue_that_did_no_work_in_the_window_is_a_device_path_fault(ecdsa_over_hmac):
    cell, _result, _seen, still = ecdsa_over_hmac
    numbers = still["numbers"]
    assert numbers["device_path_faults"] == cell.config["n"]  # once for every engine
    assert compare.verdict(numbers) is False
    assert not any(v for k, v in numbers.items() if k != "device_path_faults"), numbers
    # a side without a kernel file (HMAC certificates are made on the host) is asked nothing
    assert all(d["items"]["hmac_sha256", "sign"] == 0 for d in still["deltas"])


# -- (e) verify_skipped -------------------------------------------------------


def test_verify_skipped_enters_the_skip_of_every_verify_kernel_and_leaves_it():
    entered, left = [], []

    def kernel(name, kind):
        @contextlib.contextmanager
        def skip():
            entered.append(name)
            try:
                yield
            finally:
                left.append(name)

        return types.SimpleNamespace(KIND=kind, skip=skip)

    system = types.SimpleNamespace(kernels={
        "ecdsa_verify": kernel("ecdsa_verify", "verify"), "ecdsa_sign": kernel("ecdsa_sign", "sign"),
        "hmac_verify": kernel("hmac_verify", "verify")})

    async def sabotage():
        async with controls.sabotaged(system, "verify_skipped"):
            assert entered == ["ecdsa_verify", "hmac_verify"] and not left
            raise RuntimeError("the window failed")

    with pytest.raises(RuntimeError, match="the window failed"):
        asyncio.run(sabotage())
    assert sorted(left) == ["ecdsa_verify", "hmac_verify"]


def test_the_accepted_verify_kernels_skip_patches_the_entry_the_engine_calls():
    import numpy as np

    from minbft_tpu.ops import p256

    kernel = manifest.load_kernels(manifest.load_cell("n3f1-ecdsa.closed-16x8"))["ecdsa_verify"]
    sound = p256.ecdsa_verify_kernel_packed
    with kernel.skip():
        assert p256.ecdsa_verify_kernel_packed(np.zeros((8, p256.PACKED_COLS), np.uint16)).all()
    assert p256.ecdsa_verify_kernel_packed is sound
