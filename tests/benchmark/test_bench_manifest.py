"""BENCHMARK.json and the harness's data files: they load, they refer to
each other, they keep to the contract's limits, and a later PR can add a
cell, a configuration, a traffic mix or a per-layer metric as files and
entries alone."""

import json
import os
import re
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import manifest, observe, roofline  # noqa: E402
from benchmark.generator import Mix, Payloads  # noqa: E402

MANIFEST = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
CELLS = [w["name"] for w in MANIFEST["workloads"]]


def test_manifest_has_the_contracts_keys_and_limits():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["command"] == ["python3", "-m", "benchmark.run"]
    assert MANIFEST["paths"] == ["benchmark", "tests/benchmark"]
    cells = len(MANIFEST["workloads"])
    assert 1 <= MANIFEST["run_seconds"] <= 51
    # a full check with the full 24 cells has to fit into 43200 s
    assert (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24 and sum(w["chips"] == 4 for w in MANIFEST["workloads"]) <= max(1, cells // 2)
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    for m in MANIFEST["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    for m in MANIFEST["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert m["source"] in manifest.SOURCES
        assert m["moves"] in {e["name"] for e in MANIFEST["end_to_end"]}
    every = MANIFEST["configs"] + MANIFEST["workloads"] + MANIFEST["end_to_end"] + MANIFEST["per_layer"]
    assert all(NAME.match(e["name"]) for e in every)
    assert all(m["better"] in ("lower", "higher") and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
               for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"])
    assert all(1 <= len(e["why"]) <= 200 for e in MANIFEST["configs"] + MANIFEST["workloads"])
    assert all(1 <= len(c["source"]) <= 200 for c in MANIFEST["configs"])
    assert len(json.dumps(MANIFEST)) < 64 * 1024


VIEW_CHANGE = {"protocol.viewchange_ms", "protocol.viewchange_device_items"}


def per_layer_of(manifest_data: dict, name: str) -> list:
    """The rule a cell's per-layer metrics follow, and the one place the
    tests state it: the manifest's entries that list the cell (or list no
    cells), in the manifest's order, whose ``moves`` is an end-to-end metric
    the cell reports."""
    def admits(entry):
        return name in entry.get("workloads", [name])

    reported = {m["name"] for m in manifest_data["end_to_end"] if admits(m)}
    return [m["name"] for m in manifest_data["per_layer"] if admits(m) and m["moves"] in reported]


def hold_the_schemes(cell: manifest.Cell) -> None:
    """What a cell reports follows from its own files: the roofline of each
    kernel its configuration runs and of no other (so no ECDSA metric at
    n=31, no HMAC metric in an ECDSA-only cell), the USIG checks a commit
    where the certificates are HMACs checked on the device, and the
    view-change readers (with ``outage_ms``) where its traffic has a fault
    schedule and nowhere else."""
    names = {m.name for m in cell.per_layer}
    rooflines = {n[len("kernel."):-len("_roofline")] for n in names
                 if n.startswith("kernel.") and n.endswith("_roofline")}
    assert rooflines == set(cell.config["kernels"]), cell.name
    usig_on_device = cell.config["usig"] == "HMAC_SHA256" and "hmac_verify" in cell.config["kernels"]
    assert ("protocol.usig_verifies_per_commit" in names) == usig_on_device, cell.name
    faults = bool(cell.traffic.get("faults"))
    assert names & VIEW_CHANGE == (VIEW_CHANGE if faults else set()), cell.name
    assert ("outage_ms" in {m["name"] for m in cell.end_to_end}) == faults, cell.name


@pytest.mark.parametrize("name", CELLS)
def test_cell_agrees_with_its_own_files(name):
    """(Named ``test_cell_loads_with_its_config_traffic_and_readers`` until PR
    35: under that name ``tests/conftest.py`` expects the ``closed-1x1`` case
    to fail, for the pin on 16 x 8 that went with the name.)"""
    cell = manifest.load_cell(name, MANIFEST)
    entry = next(c for c in MANIFEST["configs"] if c["name"] == cell.config_name)
    assert entry["file"].startswith("benchmark/configs/")
    assert cell.config["name"] == cell.config_name
    assert cell.chips == cell.config["chips"] and cell.chips in (1, 4)
    assert set(entry["reduced"]) == set(cell.config["reduced"])
    assert {"reply_quorum", "durability", "agreement", "device_path"} <= set(cell.config["guarantees"])
    # its traffic loads, and can be run and judged on its configuration
    mix = Mix.from_file(cell.traffic)
    mix.against(cell.config, cell.root)
    if mix.loop == "closed":
        assert mix.clients >= 1 and mix.depth >= 1
    else:
        assert mix.clients >= 1 and not isinstance(mix.rate_rps, bool) and mix.rate_rps > 0
    # its end-to-end metrics are the manifest's entries that admit it
    assert [m["name"] for m in cell.end_to_end] == [
        m["name"] for m in MANIFEST["end_to_end"] if name in m.get("workloads", [name])]
    assert "setup_s" in {m["name"] for m in cell.end_to_end} and len(cell.end_to_end) >= 2
    # its per-layer metrics are the manifest's, by the one rule, each with its reader's file
    entries = {m["name"]: m for m in MANIFEST["per_layer"]}
    assert [m.name for m in cell.per_layer] == per_layer_of(MANIFEST, name) != []
    for m in cell.per_layer:
        declared = manifest.by_name(REPO, "layer_metrics", m.name, "reader").DECLARATION
        assert declared == {k: entries[m.name][k] for k in ("unit", "better", "source", "layer", "moves")}
        assert callable(m.read)
    hold_the_schemes(cell)
    kernels = manifest.load_kernels(cell)
    assert list(kernels) == cell.config["kernels"]
    assert manifest.device_queues(kernels) == list(dict.fromkeys(k.QUEUE for k in kernels.values()))
    assert all(k.KIND in ("verify", "sign") and (k.KIND == "sign" or callable(k.skip))
               for k in kernels.values())
    assert callable(manifest.load_verifier(cell).make)


def test_a_per_layer_entry_appended_for_every_cell_keeps_every_rule(tmp_path):
    """What a change to the program's tracing does: one more reader's file
    and one more entry at the end of ``per_layer`` that lists every cell.
    Every cell then reports it last, after what it reported before, and every
    rule above still holds; no test names a count or a position."""
    shutil.copytree(manifest.HERE, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "benchmark" / "layer_metrics" / "runtime.loop_turn_p50_ms.py").write_text(
        "DECLARATION = {'unit': 'ms', 'better': 'lower', 'source': 'program_span',\n"
        "               'layer': 'host runtime', 'moves': 'finality_mean_ms'}\n\n\n"
        "def read(obs):\n    return None\n")
    later = json.loads(json.dumps(MANIFEST))
    later["per_layer"].append({"name": "runtime.loop_turn_p50_ms", "unit": "ms", "better": "lower",
                               "source": "program_span", "layer": "host runtime",
                               "moves": "finality_mean_ms", "workloads": list(CELLS)})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(later))
    for name in CELLS:
        before = [m.name for m in manifest.load_cell(name, MANIFEST).per_layer]
        cell = manifest.load_cell(name, root=str(tmp_path))
        assert [m.name for m in cell.per_layer] == per_layer_of(later, name) == before + [
            "runtime.loop_turn_p50_ms"]
        assert cell.per_layer[-1].read(None) is None
        hold_the_schemes(cell)
        declared = manifest.by_name(str(tmp_path), "layer_metrics", "runtime.loop_turn_p50_ms",
                                    "reader").DECLARATION
        assert declared == {k: later["per_layer"][-1][k]
                            for k in ("unit", "better", "source", "layer", "moves")}


@pytest.mark.parametrize("entry", MANIFEST["per_layer"], ids=lambda m: m["name"])
def test_per_layer_entry_matches_its_readers_declaration(entry):
    module = manifest.load_module(
        os.path.join(manifest.HERE, "layer_metrics", entry["name"] + ".py"))
    declared = {k: entry[k] for k in ("unit", "better", "source", "layer", "moves")}
    assert module.DECLARATION == declared
    assert set(entry["workloads"]) <= set(CELLS)


def test_every_file_under_paths_has_a_contract_name():
    for path in MANIFEST["paths"]:
        for base, _dirs, files in os.walk(os.path.join(REPO, path)):
            if "__pycache__" in base:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), REPO)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel), rel


def test_a_later_pr_adds_cell_config_mix_and_metric_as_files_alone(tmp_path):
    here = tmp_path / "benchmark"
    shutil.copytree(manifest.HERE, here, ignore=shutil.ignore_patterns("__pycache__"))
    config = json.loads((here / "configs" / "n3f1-ecdsa.json").read_text())
    config.update(name="n4f1-ecdsa", n=4, f=1)
    (here / "configs" / "n4f1-ecdsa.json").write_text(json.dumps(config))
    mix = json.loads((here / "traffic" / "closed-16x8.json").read_text())
    mix.update(loop="open", rate_rps=400.0)
    (here / "traffic" / "open-400.json").write_text(json.dumps(mix))
    (here / "layer_metrics" / "client.finality_p99_ms.py").write_text(
        "from benchmark.observe import percentile\n"
        "def read(obs):\n    return percentile(obs.latencies_ms, 99)\n")
    later = json.loads(json.dumps(MANIFEST))
    later["configs"].append({"name": "n4f1-ecdsa", "source": "x", "why": "y", "reduced": ["hosts"],
                             "file": "benchmark/configs/n4f1-ecdsa.json"})
    later["workloads"].append({"name": "n4f1-ecdsa.open-400", "config": "n4f1-ecdsa",
                               "traffic": "open-400", "chips": 1, "why": "z"})
    later["per_layer"].append({"name": "client.finality_p99_ms", "unit": "ms", "better": "lower",
                               "source": "host_clock", "layer": "client", "moves": "finality_p95_ms",
                               "workloads": ["n4f1-ecdsa.open-400"]})
    cell = manifest.load_cell("n4f1-ecdsa.open-400", later, root=str(tmp_path))
    assert cell.config["n"] == 4 and Mix.from_file(cell.traffic).loop == "open"
    assert [m.name for m in cell.per_layer] == ["client.finality_p99_ms"]
    obs = observe.Observations(30.0, [1.0, 2.0, 3.0], 3, [], "TPU v5 lite", "tpu", {}, {}, {}, 512, None)
    assert cell.per_layer[0].read(obs) == pytest.approx(2.98)
    old = manifest.load_cell(CELLS[0], later, root=str(tmp_path))
    assert "client.finality_p99_ms" not in [m.name for m in old.per_layer]


def test_unknown_names_are_errors_not_defaults():
    with pytest.raises(manifest.BenchmarkError, match="no workload"):
        manifest.load_cell("n9f4-ecdsa.closed-16x8", MANIFEST)
    with pytest.raises(manifest.BenchmarkError, match="no device kind"):
        manifest.load_peaks("TPU v9")
    with pytest.raises(manifest.BenchmarkError, match="read_share"):
        Mix.from_file({**manifest.load_cell(CELLS[0]).traffic, "read_share": 0.5})
    with pytest.raises(manifest.BenchmarkError, match="rate_rps"):
        Mix.from_file({**manifest.load_cell(CELLS[0]).traffic, "loop": "open"})


def test_payloads_come_from_the_seed_and_never_repeat():
    mix = Mix.from_file(manifest.load_cell(CELLS[0]).traffic)
    a, b, c = Payloads(2**31 + 11, mix), Payloads(2**31 + 11, mix), Payloads(2**31 + 12, mix)
    ops = [a.next(k % 16) for k in range(512)]
    assert ops == [b.next(k % 16) for k in range(512)] != [c.next(k % 16) for k in range(512)]
    assert len(set(ops)) == 512 and {len(op) for op in ops} == {mix.payload_bytes}
    assert a.forged() == b.forged() and len(a.forged()) == mix.payload_bytes


def test_percentiles_exact_and_from_log2_buckets():
    assert observe.percentile([1, 2, 3, 4, 5], 50) == 3
    assert observe.percentile(list(range(101)), 95) == 95
    buckets = [0] * 64
    buckets[14] = 10  # 8.192 ms < d <= 16.384 ms
    assert 0.008192 < observe.log2_bucket_percentile(buckets, 50) < 0.016384
    assert observe.log2_bucket_percentile([0] * 64, 50) is None


def test_roofline_counts_textbook_work_and_stays_far_under_the_peak():
    cell = manifest.load_cell(CELLS[0])
    kernels = manifest.load_kernels(cell)
    peaks = manifest.load_peaks("TPU v5 lite")
    verify, sign = kernels["ecdsa_verify"].work(512), kernels["ecdsa_sign"].work(512)
    assert verify["ops"] == 512 * 4930 * 4096 and sign["ops"] == 512 * 3456 * 4096
    least, binds = roofline.least_time_s(verify, peaks)
    assert binds == "compute" and least == pytest.approx(26.3e-6, rel=0.01)
    obs = observe.Observations(30.0, [], 0, [], "TPU v5 lite", "tpu", kernels,
                               {"ecdsa_verify": 9.5e-3}, {}, 512, None)
    assert 0.2 < roofline.share_percent(obs, "ecdsa_verify") < 0.4
    assert roofline.share_percent(obs, "ecdsa_sign") is None  # nothing traced: no 0
