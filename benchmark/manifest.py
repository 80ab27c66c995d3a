"""BENCHMARK.json and the data files it names, loaded and cross-checked.

Nothing here touches JAX: the tests and the entry point read the manifest
before any backend starts.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


class BenchmarkError(Exception):
    """The benchmark cannot run as asked; the process exits non-zero."""


def read_json(path: str) -> dict:
    try:
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)
    except (OSError, ValueError) as e:
        raise BenchmarkError(f"cannot read {path}: {e}") from e


def load_manifest(root: str = ROOT) -> dict:
    return read_json(os.path.join(root, "BENCHMARK.json"))


@dataclasses.dataclass(frozen=True)
class LayerMetric:
    """One per-layer metric: its entry in BENCHMARK.json and its reader,
    ``benchmark/layer_metrics/<name>.py``.  The reader's ``read(obs)``
    returns the number, or None where it found nothing to read."""

    name: str
    unit: str
    better: str
    source: str
    layer: str
    moves: str
    read: Callable[[object], Optional[float]]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict  # benchmark/configs/<config>.json as it is run
    traffic_name: str
    traffic: dict  # benchmark/traffic/<traffic>.json
    end_to_end: List[dict]  # the manifest's entries that this cell reports
    per_layer: List[LayerMetric]


def _in_cell(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_reader(name: str) -> Callable[[object], Optional[float]]:
    """``read`` of benchmark/layer_metrics/<name>.py (a metric's name may
    hold dots, so the file is loaded by path, not imported by name)."""
    module = load_module(os.path.join(HERE, "layer_metrics", name + ".py"))
    return module.read


def load_module(path: str):
    if not os.path.isfile(path):
        raise BenchmarkError(f"no such file: {path}")
    tag = os.path.splitext(os.path.basename(path))[0].replace(".", "_")
    spec = importlib.util.spec_from_file_location(f"benchmark._byname_{tag}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_kernel(name: str):
    """benchmark/kernels/<name>.py: the kernel's jit name in a trace and the
    textbook work and bytes of one dispatch."""
    return load_module(os.path.join(HERE, "kernels", name + ".py"))


def load_peaks(device_kind: str) -> dict:
    peaks = read_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in peaks["devices"]:
        raise BenchmarkError(
            f"benchmark/peaks.json has no device kind {device_kind!r} "
            f"(it has {sorted(peaks['devices'])}): add its published peaks"
        )
    return peaks["devices"][device_kind]


def load_cell(name: str, manifest: Optional[dict] = None) -> Cell:
    manifest = manifest or load_manifest()
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchmarkError(f"no workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in configs:
        raise BenchmarkError(f"workload {name}: no config {w['config']!r}")
    config = read_json(os.path.join(ROOT, configs[w["config"]]["file"]))
    traffic = read_json(os.path.join(HERE, "traffic", w["traffic"] + ".json"))
    e2e = [m for m in manifest["end_to_end"] if _in_cell(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [
        LayerMetric(
            m["name"], m["unit"], m["better"], m["source"], m["layer"],
            m["moves"], load_reader(m["name"]),
        )
        for m in manifest["per_layer"]
        if _in_cell(m, name) and m["moves"] in reported
    ]
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, e2e, per_layer)


def load_kernels(cell: Cell) -> Dict[str, object]:
    """The kernels this cell's configuration dispatches, by the config's
    ``kernels`` list -> {name: module of benchmark/kernels/<name>.py}."""
    return {k: load_kernel(k) for k in cell.config["kernels"]}
