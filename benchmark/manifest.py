"""BENCHMARK.json and the data files it names, loaded and cross-checked.

Nothing here touches JAX: the tests and the entry point read the manifest
before any backend starts.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
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


def by_name(root: str, kind: str, name: str, what: str):
    """The module of ``<root>/benchmark/<kind>/<name>.py``: one file for
    each reader, kernel and reference verifier, found by its name (which
    may hold dots and dashes, so it is loaded by path, not imported)."""
    path = os.path.join(root, "benchmark", kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchmarkError(f"no {what} {name!r}: add the file {path}")
    return load_module(path)


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
    root: str = ROOT  # the checkout whose files the cell was loaded from


def _in_cell(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_module(path: str):
    if not os.path.isfile(path):
        raise BenchmarkError(f"no such file: {path}")
    tag = re.sub(r"\W", "_", os.path.splitext(os.path.basename(path))[0])
    spec = importlib.util.spec_from_file_location(f"benchmark._byname_{tag}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_peaks(device_kind: str) -> dict:
    peaks = read_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in peaks["devices"]:
        raise BenchmarkError(
            f"benchmark/peaks.json has no device kind {device_kind!r} "
            f"(it has {sorted(peaks['devices'])}): add its published peaks"
        )
    return peaks["devices"][device_kind]


def load_cell(name: str, manifest: Optional[dict] = None, root: str = ROOT) -> Cell:
    """The cell ``name`` of ``manifest`` (default: ``root``'s BENCHMARK.json)
    with its files, all found under ``root``."""
    manifest = manifest or load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchmarkError(f"no workload {name!r}; BENCHMARK.json has {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    if w["config"] not in configs:
        raise BenchmarkError(f"workload {name}: no config {w['config']!r}")
    config = read_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = read_json(os.path.join(root, "benchmark", "traffic", w["traffic"] + ".json"))
    e2e = [m for m in manifest["end_to_end"] if _in_cell(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [
        LayerMetric(
            m["name"], m["unit"], m["better"], m["source"], m["layer"], m["moves"],
            by_name(root, "layer_metrics", m["name"], "reader of the per-layer metric").read,
        )
        for m in manifest["per_layer"]
        if _in_cell(m, name) and m["moves"] in reported
    ]
    return Cell(name, int(w["chips"]), w["config"], config, w["traffic"],
                traffic, e2e, per_layer, root)


def load_kernels(cell: Cell) -> Dict[str, object]:
    """The kernels this cell's configuration dispatches, by the config's
    ``kernels`` list -> {name: module of benchmark/kernels/<name>.py}: the
    engine queue it runs in (``QUEUE``, the key of ``engine.stats`` /
    ``engine.sign_stats``) and on which side of it (``KIND``), its jit name
    in a trace, the textbook work and bytes of one dispatch, one dispatch
    through an engine, and for a verify kernel ``skip()``, the control."""
    return {k: by_name(cell.root, "kernels", k, "kernel") for k in cell.config["kernels"]}


def device_queues(kernels: Dict[str, object]) -> List[str]:
    """A configuration's device queues: its kernels' distinct ``QUEUE``s,
    in the order of its ``kernels`` list."""
    return list(dict.fromkeys(module.QUEUE for module in kernels.values()))


def load_verifier(cell: Cell):
    """benchmark/verifiers/<scheme>.py, the plain reference for the scheme the
    configuration's replicas sign replies with: ``make(replica_pubs)`` ->
    ``valid(replica_id, msg, signature) -> bool``."""
    return by_name(cell.root, "verifiers", cell.config["scheme"],
                   "reference verifier for the scheme")
