"""Crash-recovery soak over a REAL ``peer run`` process cluster (ISSUE 20).

The recovery subsystem's whole claim is about surviving SIGKILL — so its
acceptance harness runs actual OS processes, not an in-process cluster:
scaffold a testnet, run every replica with a durable ``--state-dir``
(and optionally under the seeded chaos wrap), drive pipelined client
load, ``kill -9`` one replica MID-LOAD, restart it against the same
store, and read the recovery clock off the restarted replica's own
``minbft_recovery_*`` Prometheus families.

What one soak run proves (``run_recovery_soak`` raises on any miss):

- **Zero committed loss** — every request the bench fired commits;
  a kill/restart cycle may slow the cluster, never un-commit it.
- **Durable restore happened** — the restarted replica reports
  ``minbft_recovery_restored_count`` (it resumed from its store, not a
  cold state fetch) and a finite ``minbft_recovery_time_ms``.
- **Store invariants** — every surviving store file decodes, its f+1
  certificate is structurally valid, and its snapshot recomputes to the
  certified digest (:class:`~minbft_tpu.testing.invariants.RecoveryInvariantChecker`).
- **Census honesty** (chaos mode) — each replica's live injected-fault
  census equals the count replayed from the seed and its recorded
  per-link frame totals alone: the faults the soak survived were
  exactly the deterministic schedule, no more, no fewer.

The report dict feeds the bench's ``chaos_recovery_*`` keys, which
``tools/benchgate`` gates (recovery-time on INCREASE, under-recovery
goodput on DROP) — the recovery-time SLO is a number in CI, not prose.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from typing import Dict, Optional

from .faultnet import SEEDED_KINDS, FaultNet, plan_from_spec
from .invariants import InvariantViolation, RecoveryInvariantChecker

#: Default chaos plan for the pinned soak: mild loss + delay so the
#: transfer/catch-up paths see real adversity without severing the
#: cluster (the soak asserts 100% commit).
DEFAULT_SOAK_PLAN = "drop=0.01,delay=0.05,duplicate=0.01"


def _peer_cmd(workdir: str, *tail: str) -> list:
    return [
        sys.executable, "-m", "minbft_tpu.sample.peer",
        "--keys", f"{workdir}/keys.yaml",
        "--config", f"{workdir}/consensus.yaml",
        "--transport", "tcp", *tail,
    ]


def _metrics_port(log_path: str, offset: int, timeout: float) -> int:
    """Parse the ``--metrics-port 0`` announcement from a replica's
    stderr log, reading only bytes past ``offset`` (a restarted replica
    appends a SECOND announcement to the same file)."""
    import re

    deadline = time.time() + timeout
    while time.time() < deadline:
        with open(log_path, "rb") as fh:
            fh.seek(offset)
            m = re.search(rb"metrics on http://[^:]+:(\d+)/metrics", fh.read())
        if m:
            return int(m.group(1))
        time.sleep(0.25)
    raise AssertionError(f"{log_path} never announced its metrics endpoint")


def _scrape_families(addr: str, timeout: float = 5.0) -> dict:
    from ..obs.prom import parse_exposition, scrape

    return parse_exposition(scrape(addr, timeout=timeout))


def _gauge(fams: dict, name: str) -> Optional[float]:
    fam = fams.get(name)
    if not fam or not fam["samples"]:
        return None
    return next(iter(fam["samples"].values()))


def _census_from_scrape(fams: dict) -> dict:
    """Rebuild (seeded counts, per-link frames) from the faultnet
    exposition families."""
    seeded = {k: 0 for k in SEEDED_KINDS}
    fam = fams.get("minbft_faultnet_injected_total")
    for key, v in (fam["samples"] if fam else {}).items():
        kind = dict(key).get("kind")
        if kind in seeded:
            seeded[kind] = int(v)
    frames: Dict[tuple, int] = {}
    fam = fams.get("minbft_faultnet_frames_total")
    for key, v in (fam["samples"] if fam else {}).items():
        link = dict(key).get("link", "")
        src, _, dst = link.partition(">")
        if src and dst:
            frames[(src, dst)] = int(v)
    return {"seeded": seeded, "frames": frames}


def run_recovery_soak(
    workdir: str,
    *,
    replicas: int = 4,
    requests: int = 200,
    clients: int = 8,
    depth: int = 4,
    kill_target: int = 3,
    checkpoint_period: int = 8,
    chunk_bytes: int = 4096,
    chaos_seed: Optional[int] = None,
    chaos_plan: str = "",
    down_s: float = 1.0,
    bench_timeout_s: float = 420.0,
) -> dict:
    """Run one kill-9-mid-load recovery soak; returns the report dict.

    Raises AssertionError/InvariantViolation on any acceptance miss —
    the caller (pytest, the bench phase, the CI tier) only has to
    propagate.  ``chaos_seed=None`` runs without the network-fault wrap
    (process chaos only); a pinned seed makes the whole fault schedule
    replayable and turns on the census-equality check.

    Size ``requests`` so the load OUTLIVES the outage: the recovery
    clock stops at the restarted replica's first executed request, and
    a bench that drains while the replica is still rebooting (a python
    interpreter restart is seconds) leaves the clock running until the
    180s wait gives up.  ~30s+ of load at the host's committed rate is
    the safe floor.
    """
    from ..recovery import store_path
    from ..utils.netports import free_base_port, wait_ports
    from .faultnet import ProcessChaos

    f = (replicas - 1) // 2
    state_dir = os.path.join(workdir, "state")
    base_port = free_base_port(replicas)

    # The peer subprocesses must import this checkout regardless of the
    # caller's cwd.
    repo_root = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    env = dict(
        os.environ,
        PYTHONPATH=repo_root
        + os.pathsep
        + os.environ.get("PYTHONPATH", ""),
        CONSENSUS_TIMEOUT_REQUEST="60s",
        CONSENSUS_TIMEOUT_PREPARE="30s",
        CONSENSUS_CHECKPOINT_PERIOD=str(checkpoint_period),
        MINBFT_STATE_DIR=state_dir,
        MINBFT_RECOVERY_CHUNK_BYTES=str(chunk_bytes),
        # One process per chip: the caller (a bench or a test that has
        # touched JAX) may hold it, and these --no-batch children need
        # none — pinned to the CPU platform they never load the TPU
        # library at all.
        JAX_PLATFORMS="cpu",
    )
    env.pop("MINBFT_CHAOS_SEED", None)
    env.pop("MINBFT_CHAOS_PLAN", None)
    plan_spec = ""
    if chaos_seed is not None:
        plan_spec = chaos_plan or DEFAULT_SOAK_PLAN
        env["MINBFT_CHAOS_SEED"] = hex(chaos_seed)
        env["MINBFT_CHAOS_PLAN"] = plan_spec

    scaffold = subprocess.run(
        [sys.executable, "-m", "minbft_tpu.sample.peer", "testnet",
         "-n", str(replicas), "-d", workdir, "--base-port", str(base_port),
         "--clients", str(clients), "--usig", "SOFT_ECDSA"],
        env=env, capture_output=True, text=True, timeout=120,
    )
    assert scaffold.returncode == 0, scaffold.stderr

    chaos = ProcessChaos()
    logs = []

    def start_replica(i: int):
        log = open(f"{workdir}/replica{i}.log", "ab")
        logs.append(log)
        return subprocess.Popen(
            _peer_cmd(workdir, "run", str(i), "--no-batch",
                      "--metrics-port", "0"),
            env=env, stdout=subprocess.DEVNULL, stderr=log,
        )

    report: dict = {
        "requested": 0, "committed": 0, "chaos_seed": chaos_seed,
        "chaos_plan": plan_spec,
    }
    bench = None
    try:
        for i in range(replicas):
            chaos.manage(f"r{i}", lambda i=i: start_replica(i))
        assert wait_ports(
            [base_port + i for i in range(replicas)]
        ), "replicas never bound"
        mports = {
            i: _metrics_port(f"{workdir}/replica{i}.log", 0, 30)
            for i in range(replicas)
        }

        bench = subprocess.Popen(
            _peer_cmd(workdir, "bench", "--clients", str(clients),
                      "--requests", str(requests), "--depth", str(depth),
                      "--tag", "soak"),
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True,
        )

        # Kill only once the target has something durable to lose: its
        # store file exists after the first stable checkpoint persists.
        target_store = store_path(state_dir, kill_target)
        deadline = time.time() + 120
        while time.time() < deadline and not os.path.exists(target_store):
            assert bench.poll() is None, "bench finished before any " \
                "stable checkpoint persisted — raise requests or lower " \
                "checkpoint_period"
            time.sleep(0.25)
        assert os.path.exists(target_store), (
            f"replica {kill_target} never persisted a stable checkpoint"
        )

        # THE event: SIGKILL mid-load, a short outage, restart against
        # the same store.  The restarted replica must restore, catch up,
        # and execute again — its own metrics are the recovery clock.
        log_off = os.path.getsize(f"{workdir}/replica{kill_target}.log")
        t_kill = time.monotonic()
        chaos.kill(f"r{kill_target}")
        time.sleep(down_s)
        chaos.restart(f"r{kill_target}")
        assert wait_ports(
            [base_port + kill_target]
        ), "restarted replica never bound"
        mports[kill_target] = _metrics_port(
            f"{workdir}/replica{kill_target}.log", log_off, 30
        )

        addr = f"127.0.0.1:{mports[kill_target]}"
        restored = recovery_ms = None
        deadline = time.time() + 180
        while time.time() < deadline:
            try:
                fams = _scrape_families(addr)
            except OSError:
                time.sleep(0.5)
                continue
            restored = _gauge(fams, "minbft_recovery_restored_count")
            recovery_ms = _gauge(fams, "minbft_recovery_time_ms")
            if recovery_ms is not None:
                break
            time.sleep(0.5)
        assert restored is not None, (
            "restarted replica never reported minbft_recovery_restored_count "
            "— it did not restore from its durable store"
        )
        assert recovery_ms is not None, (
            "restarted replica never reported minbft_recovery_time_ms — "
            "it restored but never executed again (catch-up wedged)"
        )
        report["restored_count"] = int(restored)
        report["chaos_recovery_time_ms"] = round(float(recovery_ms), 2)
        report["wall_recovery_ms"] = round(
            (time.monotonic() - t_kill) * 1e3, 2
        )

        # Zero committed loss: the bench awaits EVERY request — a clean
        # exit with committed == requested is the loss proof.
        out, _ = bench.communicate(timeout=bench_timeout_s)
        assert bench.returncode == 0, "bench failed (request lost or wedged)"
        stats = json.loads(out.strip().splitlines()[-1])
        report["requested"] = (max(requests // clients, 1)) * clients
        report["committed"] = stats["committed"]
        assert stats["committed"] == report["requested"], (
            f"committed {stats['committed']} != requested "
            f"{report['requested']}: a committed request was lost"
        )
        report["chaos_recovery_goodput_per_sec"] = stats["req_per_sec"]

        # Durable-store invariants across every replica that persisted.
        checker = RecoveryInvariantChecker(f)
        report["stores"] = checker.check_all(
            {i: store_path(state_dir, i) for i in range(replicas)}
        )
        if kill_target not in report["stores"]:
            raise InvariantViolation(
                f"replica {kill_target}'s durable store vanished after "
                "the kill/restart cycle"
            )

        # Census equality (chaos mode): the live per-replica census must
        # equal the seed-replay over its recorded frame counts.  Scrape
        # until quiescent (two identical reads) — the census mutates
        # while checkpoint traffic drains.
        if chaos_seed is not None:
            replayer = FaultNet(
                seed=chaos_seed, default_plan=plan_from_spec(plan_spec)
            )
            census_ok = {}
            for i in range(replicas):
                a = f"127.0.0.1:{mports[i]}"
                prev = None
                deadline = time.time() + 60
                while time.time() < deadline:
                    cur = _census_from_scrape(_scrape_families(a))
                    if prev == cur:
                        break
                    prev = cur
                    time.sleep(1.0)
                replayed = replayer.replay_counts(prev["frames"])
                assert prev["seeded"] == replayed, (
                    f"replica {i}: live census {prev['seeded']} != "
                    f"seed-replayed {replayed} "
                    f"(seed {chaos_seed:#x}, plan {plan_spec})"
                )
                census_ok[i] = prev["seeded"]
            report["census"] = census_ok
        return report
    finally:
        if bench is not None and bench.poll() is None:
            bench.kill()
        chaos.terminate_all()
        for log in logs:
            log.close()
