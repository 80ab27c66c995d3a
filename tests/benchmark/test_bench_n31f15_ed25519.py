"""The deployment ``n31f15-ed25519`` (Ed25519 messages over HMAC-SHA256 USIG
certificates): its three kernels' files against counts derived by hand and
against the program they name, its reference verifier, and the real
configuration's files with ``n`` and ``f`` cut to 3 and 1 in a temporary
checkout, run on the CPU backend through the run's own window, comparison
and result line.  Nothing here builds 31 replicas but the one ``slow`` case."""

import asyncio
import hashlib
import json
import os
import shutil
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from benchmark import controls, manifest, observe, roofline, run, tracing  # noqa: E402
from test_bench_deployments import CPU, drive  # noqa: E402  (this directory)
from test_bench_manifest import hold_the_schemes  # noqa: E402  (this directory)

CELL = "n31f15-ed25519.closed-16x8"
SEED = 2**31 + 2029
FIELD_MUL = 2 * (2 * 32 * 32)  # product + reduction of 32 x 32 8-bit limbs, 2 operations a MAC
SHA256_COMPRESSION = 64 * (7 + 5 + 5 + 4 + 5) + 48 * (3 + 5 + 5) + 8  # word operations, FIPS 180-4
# By hand: (operations a lane, bytes a lane, the kernel's device seconds at
# 512 lanes from the chip's trace: my chip runs, PR 29, PERF.md section 5).
BY_HAND = {
    # 253 doublings of 8, 189 additions of 8, x^(p-2) by 254 squarings and 127 products, 2 to normalise
    "ed25519_verify": ((253 * 8 + 189 * 8 + 254 + 127 + 2) * FIELD_MUL, 64 + 64 + 32 + 1, 8.789645e-3),
    # 253 doublings of 8, 126 additions of 8
    "ed25519_sign": ((253 * 8 + 126 * 8) * FIELD_MUL, 32 + 96, 1.188256e-3),
    # two key pads of 8 words, four compressions, 8 words compared; four 8-bit operations a word
    "hmac_verify": ((16 + 4 * SHA256_COMPRESSION + 8) * 4, 32 + 32 + 32 + 1, 14.168e-6),
}


@pytest.fixture(scope="module")
def kernels():
    return manifest.load_kernels(manifest.load_cell(CELL))


# -- (a) the kernels' work ------------------------------------------------------


@pytest.mark.parametrize("name", list(BY_HAND))
def test_kernels_work_is_the_textbooks_count_and_its_share_stays_under_the_peak(kernels, name):
    ops, nbytes, chip_seconds = BY_HAND[name]
    work = kernels[name].work(512)
    assert work == {"ops": 512 * ops, "peak": "int8_ops_per_s", "bytes": 512 * nbytes}
    assert kernels[name].work(1)["ops"] * 512 == work["ops"]
    obs = observe.Observations(30.0, [], 0, [], "TPU v5 lite", "tpu", kernels,
                               {name: chip_seconds}, {}, 512, None)
    assert 0.2 < roofline.share_percent(obs, name) < 2  # 0.238 %, 1.36 %, 0.43 % on the chip
    _least, binds = roofline.least_time_s(work, manifest.load_peaks("TPU v5 lite"))
    assert binds == ("memory" if name == "hmac_verify" else "compute")


def test_the_textbook_counts_are_what_the_files_say_they_are(kernels):
    assert (SHA256_COMPRESSION, FIELD_MUL) == (2296, 4096)
    assert BY_HAND["ed25519_verify"][0] == 3919 * 4096
    assert BY_HAND["ed25519_sign"][0] == 3032 * 4096
    assert BY_HAND["hmac_verify"][0] == 9208 * 4
    # the arming dispatch of a calibration session is the cheapest kernel
    assert min(kernels, key=lambda k: kernels[k].work(1)["ops"]) == "hmac_verify"


# -- (b) the names the files give are the program's -----------------------------


def test_trace_names_are_the_names_of_the_functions_the_program_jits(kernels):
    """The files take the Ed25519 names from the program (they are laid over
    the commit before PR 29 too); here they are pinned, so that a rename in
    the program fails a test and not a traced run on the chip."""
    from minbft_tpu.ops import ed25519, hmac_sha256, p256

    jitted = {
        "ed25519_verify": (ed25519.ed25519_verify_kernel_packed, "jit__ed25519_verify_one_packed"),
        "ed25519_sign": (ed25519.rb_comb_kernel(), "jit__rb_comb_widen"),
        "hmac_verify": (hmac_sha256.hmac_verify_kernel_packed, "jit_hmac_verify_kernel_packed"),
    }
    for name, (fn, pinned) in jitted.items():
        assert kernels[name].TRACE_NAME == "jit_" + fn.__name__ == pinned, name
    accepted = manifest.load_kernels(manifest.load_cell("n7f3-ecdsa.closed-16x8"))
    assert accepted["ecdsa_verify"].TRACE_NAME == "jit_" + p256.ecdsa_verify_kernel_packed.__name__
    assert accepted["ecdsa_sign"].TRACE_NAME == "jit_" + p256.kg_comb_kernel().__name__
    # tracing.kernel_events matches by prefix: no kernel's name may begin another's
    names = [k.TRACE_NAME for k in list(kernels.values()) + list(accepted.values())]
    assert len(set(names)) == 5
    assert not any(a != b and a.startswith(b) for a in names for b in names)


# -- (c) skip() ---------------------------------------------------------------


def test_both_skips_patch_the_entry_the_engines_dispatcher_calls_and_put_it_back(kernels):
    """A forged item sent through an engine's public entry comes back
    "valid" while the kernel is skipped (no kernel runs, so nothing traces
    or compiles here), and the entry is the program's own again afterwards."""
    from minbft_tpu.ops import ed25519, hmac_sha256
    from minbft_tpu.parallel import BatchVerifier
    from minbft_tpu.utils import hostcrypto

    sound = ed25519.ed25519_verify_kernel_packed, hmac_sha256.hmac_verify_kernel_packed
    _seed, pub = hostcrypto.ed25519_keygen()
    digest = hashlib.sha256(b"forged").digest()

    async def forged_items():
        engine = BatchVerifier(max_batch=8, buckets=(8,))
        with kernels["ed25519_verify"].skip(), kernels["hmac_verify"].skip():
            assert ed25519.ed25519_verify_kernel_packed is not sound[0]
            assert hmac_sha256.hmac_verify_kernel_packed is not sound[1]
            assert ed25519.ed25519_verify_kernel_packed(np.zeros((8, ed25519.PACKED_COLS), np.uint16)).all()
            return (await engine.verify_ed25519(pub, digest, bytes(64)),
                    await engine.verify_hmac_sha256(digest, digest, bytes(32)))

    assert asyncio.run(forged_items()) == (True, True)
    assert (ed25519.ed25519_verify_kernel_packed, hmac_sha256.hmac_verify_kernel_packed) == sound


# -- the plain reference ----------------------------------------------------------


def test_the_ed25519_reference_refuses_another_replicas_key_and_a_flipped_bit():
    from cryptography.hazmat.primitives import serialization
    from cryptography.hazmat.primitives.asymmetric.ed25519 import Ed25519PrivateKey

    keys = [Ed25519PrivateKey.generate() for _ in range(2)]
    pubs = {rid: k.public_key().public_bytes(serialization.Encoding.Raw,
                                             serialization.PublicFormat.Raw)
            for rid, k in enumerate(keys)}
    valid = manifest.load_verifier(manifest.load_cell(CELL)).make(pubs)
    msg = b"REPLY, as the program authenticates it"
    signature = keys[0].sign(hashlib.sha256(msg).digest())
    assert valid(0, msg, signature)
    assert not valid(1, msg, signature)  # valid, but under replica 0's key
    assert not valid(2, msg, signature)  # no such replica
    assert not valid(0, msg, bytes([signature[0] ^ 1]) + signature[1:])
    assert not valid(0, msg + b".", signature)
    assert not valid(0, msg, signature[:63])


# -- (d) the real files, n and f cut to 3 and 1 -----------------------------------


def cut_checkout(root, n: int, f: int) -> manifest.Cell:
    """This checkout's BENCHMARK.json and benchmark/ under ``root``, the
    deployment's configuration cut to ``n`` replicas; everything else (its
    kernels' files, its readers, the cell's entry) as committed."""
    here = root / "benchmark"
    shutil.copytree(manifest.HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root / "BENCHMARK.json")
    path = here / "configs" / "n31f15-ed25519.json"
    config = json.loads(path.read_text())
    config.update(n=n, f=f)
    path.write_text(json.dumps(config))
    return manifest.load_cell(CELL, root=str(root))


@pytest.fixture(scope="module")
def cut_to_three(tmp_path_factory):
    cell = cut_checkout(tmp_path_factory.mktemp("n31f15"), 3, 1)

    async def traced(system, mix):
        # On the CPU the host's clock stands in for the device's, and two
        # dispatches of a kernel that takes a millisecond there need not agree.
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(tracing, "AGREE", float("inf"))
            return await run.measured(cell, CPU, system, mix, SEED, 1.0, True)

    async def sides(system, mix):
        engine = system.engines[0]
        return {"verify": set(engine.stats), "sign": set(engine.sign_stats)}

    async def controlled(system, mix):
        # verify_skipped last: replicas that skip verification stop agreeing
        plan = [("sound", SEED + 1), ("replies_unverified", SEED + 2), ("verify_skipped", SEED + 3)]
        return await controls.windows(system, mix, plan, 1.0, lambda line: None)

    result, engine_sides, lines = drive(cell, [traced, sides, controlled])
    return cell, result, engine_sides, {ln["step"]: ln for ln in lines}


def test_the_real_files_run_correct_with_every_kernel_counted_on_its_own_side(cut_to_three):
    cell, result, engine_sides, lines = cut_to_three
    assert (cell.config["n"], cell.config["scheme"], cell.config["usig"]) == (3, "ed25519", "HMAC_SHA256")
    assert result["correct"] is True and result["attempted"] > 0, json.dumps(result["compared"])
    assert not any(line["value"] for line in result["compared"].values())
    assert lines["sound"]["correct"] is True, lines["sound"]
    kernels = manifest.load_kernels(cell)
    assert manifest.device_queues(kernels) == ["ed25519", "hmac_sha256"]
    dispatches = result["notes"]["kernel_dispatches"]
    assert list(dispatches) == list(kernels) and all(n > 0 for n in dispatches.values()), dispatches
    # (b) each file's QUEUE / KIND is a side the program's engine has
    for name, module in kernels.items():
        assert module.QUEUE in engine_sides[module.KIND], (name, engine_sides)
    assert "hmac_sha256" not in engine_sides["sign"]  # certificates are made on the host
    assert {name for name, _s in result["breakdown"]["device_ops"]} == {
        k.TRACE_NAME for k in kernels.values()}


def test_the_cells_per_layer_metrics_are_read_and_the_usig_checks_counted(cut_to_three):
    cell, result, _sides, _lines = cut_to_three
    # which metrics the cell reports is test_bench_manifest.py's rule; of
    # the schemes' it runs Ed25519 and HMAC kernels and no ECDSA one
    hold_the_schemes(cell)
    assert not any("ecdsa" in m.name for m in cell.per_layer)
    # a rehearsal has no peaks, so no roofline; everything else is read
    assert set(result["metrics"]) == {
        m.name for m in cell.per_layer if not (m.unit == "%" and m.source == "device_trace")}
    usig = result["metrics"]["protocol.usig_verifies_per_commit"]["value"]
    assert 0 < usig < result["metrics"]["protocol.device_items_per_commit"]["value"]


def test_the_usig_metric_finds_nothing_where_there_is_no_hmac_queue():
    read = manifest.by_name(REPO, "layer_metrics", "protocol.usig_verifies_per_commit", "reader").read
    deltas = [{"items": {("ecdsa_p256", "verify"): 9, ("ecdsa_p256", "sign"): 4}}]
    obs = observe.Observations(30.0, [], 10, deltas, "TPU v5 lite", "tpu", {}, {}, {}, 512, None)
    assert read(obs) is None
    deltas = [{"items": {("hmac_sha256", "verify"): 30}}, {"items": {("hmac_sha256", "verify"): 20}}]
    obs = observe.Observations(30.0, [], 10, deltas, "TPU v5 lite", "tpu", {}, {}, {}, 512, None)
    assert read(obs) == 5.0


def test_the_controls_are_failed_by_their_own_numbers_on_the_real_files(cut_to_three):
    lines = cut_to_three[3]
    unverified = lines["replies_unverified"]
    assert unverified["correct"] is False
    assert unverified["numbers"]["wrong_results"] > 0, unverified
    assert unverified["numbers"]["acks_short_of_quorum"] > 0, unverified
    skipped = lines["verify_skipped"]
    assert skipped["correct"] is False and skipped["numbers"]["forged_executed"] > 0, skipped


# -- the full size, off the tier-1 run ----------------------------------------------


@pytest.mark.slow
def test_the_full_cluster_of_31_runs_one_window_correct_on_the_cpu_rehearsal():
    cell = manifest.load_cell(CELL)
    assert (cell.config["n"], cell.config["f"]) == (31, 15)

    async def untraced(system, mix):
        return await run.measured(cell, CPU, system, mix, SEED + 4, 2.0, False)

    (result,) = drive(cell, [untraced])
    assert result["correct"] is True and result["attempted"] > 0, json.dumps(result["compared"])
    assert not any(line["value"] for line in result["compared"].values())
