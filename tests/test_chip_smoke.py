"""chip_smoke.py's phases at a tiny size on the CPU backend, and the
script's contract where there is no chip.

The phase functions take a test-only ``Size``; the script itself always
runs ``FULL`` on the chip.  What these tests cannot show — that the block
lowering runs on a TPU and a cluster commits through it — is the script's
own job (`chiprun -- python chip_smoke.py`)."""

import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Size(
    platform="cpu", lowering="loop", bucket=8, n=4, f=1, clients=2,
    requests=20, depth=4, reads=4, deploy_requests=5, mesh_lanes=16,
    groups=4, group_requests=3,
)
SEED = 0x5EED


@pytest.fixture(autouse=True)
def _auto_lowering():
    yield
    from minbft_tpu.ops import lowering

    lowering.set_mode(None)  # the phases force theirs; leave none behind


def test_device_phase_describes_the_backend(tmp_path):
    out = chip_smoke.phase_device(SEED, TINY, str(tmp_path))
    assert out == {"platform": "cpu", "kind": jax.devices()[0].device_kind,
                   "count": len(jax.devices())}
    with pytest.raises(chip_smoke.SmokeFailure, match="no tpu"):
        chip_smoke.phase_device(SEED, chip_smoke.FULL, str(tmp_path))


def test_kernels_phase_matches_host_verdicts(tmp_path, capsys):
    out = chip_smoke.phase_kernels(SEED, TINY, str(tmp_path))
    assert set(out["kernels"]) == {
        "ecdsa_verify", "hmac_verify", "ed25519_verify", "ecdsa_sign",
        "ed25519_sign",
    }
    text = capsys.readouterr().out
    assert "corrupted lanes" in text and "cpu" in text


def test_kernels_phase_refuses_the_wrong_platform(tmp_path):
    # FULL wants a tpu; on this backend the phase fails before any kernel.
    with pytest.raises(chip_smoke.SmokeFailure, match="not tpu"):
        chip_smoke.phase_kernels(SEED, chip_smoke.FULL, str(tmp_path))


def test_cluster_phase_commits_through_per_replica_device_engines(tmp_path, capsys):
    out = chip_smoke.phase_cluster(SEED, TINY, str(tmp_path))
    assert out["requests"] == 20 and out["reads"] == 4
    assert out["verify_items"] > 0 and out["sign_items"] > 0
    assert "0 host-fallback items" in capsys.readouterr().out


def test_engine_check_fails_on_host_fallback_and_timeouts():
    from minbft_tpu.parallel.engine import BatchVerifier, SignStats, VerifyStats

    engine = BatchVerifier(max_batch=8)
    v = engine._queue("ecdsa_p256", engine._dispatch_ecdsa)
    s = engine._sign_queue("ecdsa_p256", engine._dispatch_sign_ecdsa)
    v.stats = VerifyStats(items=5, batches=1, key_table_hits=5)
    s.stats = SignStats(items=5, batches=1)
    assert chip_smoke.check_engine_on_device("e", engine, {})["verify_items"] == 5
    # nothing since the baseline = the device did no protocol work
    with pytest.raises(chip_smoke.SmokeFailure, match="no ecdsa_p256 verify"):
        chip_smoke.check_engine_on_device("e", engine, {"verify_items": 5})
    s.stats.host_fallback_items = 1
    with pytest.raises(chip_smoke.SmokeFailure, match="fell back to the host"):
        chip_smoke.check_engine_on_device("e", engine, {})
    s.stats.host_fallback_items = 0
    v.stats.dispatch_timeouts = 1
    with pytest.raises(chip_smoke.SmokeFailure, match="dispatch timeouts"):
        chip_smoke.check_engine_on_device("e", engine, {})
    v.stats.dispatch_timeouts = 0
    v.stats.key_table_builds = 1  # a key the store's priming did not name
    with pytest.raises(chip_smoke.SmokeFailure, match="were not primed"):
        chip_smoke.check_engine_on_device("e", engine, {})
    v.stats.key_table_builds = 0
    v._device_written_off = True
    assert engine.written_off() == ["ecdsa_p256"]
    with pytest.raises(chip_smoke.SmokeFailure, match="written off"):
        chip_smoke.check_engine_on_device("e", engine, {})


@pytest.mark.slow
def test_multichip_phase_on_four_virtual_devices(tmp_path, capsys):
    """The `--chips 4` rehearsal on four of conftest's virtual CPU devices
    (`pytest -m slow tests/test_chip_smoke.py`).  Slow because an
    executable belongs to its device: every pinned engine compiles the
    ECDSA kernels again for its own chip, about a minute each on the CPU
    backend — so it is run before a four-chip call, not in tier 1."""
    out = chip_smoke.phase_multichip(SEED, TINY, str(tmp_path))
    assert out["mesh"] == {"lanes": 16, "shard_devices": 4}
    assert out["pool"]["placement"] == {"0": 0, "1": 1, "2": 2, "3": 3}
    assert all(v > 0 for v in out["pool"]["verify_per_chip"])
    assert len(set(out["pool"]["homes"])) == 4
    assert "equal the chips=1 run" in capsys.readouterr().out


def test_deployment_phase_refuses_a_replica_without_device_engine(tmp_path):
    """On the CPU `peer run` chooses host crypto — and says so; the phase
    commits its requests through the four processes, holds the results to
    the serial replay, and then fails on exactly that line: nothing hides
    a missing device."""
    with pytest.raises(chip_smoke.SmokeFailure) as e:
        chip_smoke.phase_deployment(SEED, TINY, str(tmp_path))
    assert "without a cpu device engine: host crypto (JAX_PLATFORMS=cpu)" in str(e.value)
    for i in range(4):
        log = (tmp_path / "testnet" / f"replica{i}.log").read_text()
        assert f"replica {i} crypto: host crypto" in log
    assert "(--no-batch)" in (tmp_path / "testnet" / "replica1.log").read_text()


def _fams(verify=7, sign=3, verify_timeouts=0, sign_timeouts=0):
    from minbft_tpu.obs.prom import parse_exposition

    return parse_exposition(f"""\
# TYPE minbft_verify_queue_items_total counter
minbft_verify_queue_items_total{{queue="ecdsa_p256",replica="0"}} {verify}
minbft_verify_queue_items_total{{queue="ecdsa_p256_host",replica="0"}} 99
# TYPE minbft_sign_queue_items_total counter
minbft_sign_queue_items_total{{queue="ecdsa_p256",replica="0"}} {sign}
# TYPE minbft_verify_queue_dispatch_timeouts_total counter
minbft_verify_queue_dispatch_timeouts_total{{queue="ecdsa_p256",replica="0"}} {verify_timeouts}
# TYPE minbft_sign_queue_dispatch_timeouts_total counter
minbft_sign_queue_dispatch_timeouts_total{{queue="ecdsa_p256",replica="0"}} {sign_timeouts}
""")


@pytest.mark.parametrize(
    "kw,complaint",
    [
        ({}, None),
        ({"verify": 0}, "no device ECDSA verify items"),
        ({"sign": 0}, "no device ECDSA sign items"),
        ({"verify_timeouts": 1}, "verify_queue_dispatch_timeouts_total = 1"),
        ({"sign_timeouts": 2}, "sign_queue_dispatch_timeouts_total = 2"),
    ],
)
def test_device_metrics_check_reads_the_engine_families(kw, complaint):
    if complaint is None:
        assert chip_smoke.check_device_metrics(_fams()) == {
            "verify_items": 7, "sign_items": 3,
        }
    else:
        with pytest.raises(chip_smoke.SmokeFailure, match=complaint):
            chip_smoke.check_device_metrics(_fams(**kw))


def test_device_metrics_check_needs_the_families():
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_device_metrics({})


def _run_script(tmp_path, *args, cwd=REPO, script=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, script or os.path.join(REPO, "chip_smoke.py"),
         "--out", str(tmp_path / "out"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("args", [(), ("--chips", "4")])
def test_script_without_a_tpu_fails_and_says_so(tmp_path, args):
    res = _run_script(tmp_path, *args)
    assert res.returncode != 0
    last = json.loads(res.stdout.strip().splitlines()[-1])
    assert last == {"ok": False, "device": None, "failed": "device"}
    # it stopped at the first phase: no CPU retry, nothing after it
    assert res.stdout.count("chip_smoke: phase ") == 1
    assert '"ok": true' not in res.stdout


def test_script_alone_in_a_directory_fails(tmp_path):
    import shutil

    bare = tmp_path / "bare"
    bare.mkdir()
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), bare)
    res = _run_script(tmp_path, cwd=str(bare), script=str(bare / "chip_smoke.py"))
    assert res.returncode != 0
    assert '"ok": true' not in res.stdout


def test_parent_never_imports_jax():
    code = (
        "import sys, chip_smoke\n"
        "bad = [m for m in ('jax', 'jaxlib', 'numpy') if m in sys.modules]\n"
        "print(bad)\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=60,
    )
    assert res.returncode == 0, res.stderr
    assert res.stdout.strip() == "[]"
