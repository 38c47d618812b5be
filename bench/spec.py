"""Finds what ``BENCHMARK.json`` names: a cell's configuration, its traffic
mix, its kernels' input regions and the reader of each metric.

Everything a cell needs is a file of its own, found by name:

* ``BENCHMARK.json``            the cells, configurations and metrics;
* ``bench/configs/<config>.json``  the fabric and the mapper's budget;
* ``bench/workloads/<cell>.json``  the traffic mix: kernels, job size
  and batch;
* ``bench/kernels/<kernel>.json``  the input regions a kernel reads;
* ``bench/metrics/<metric>.py``    ``read(record)``, the metric's reader.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List

from .traffic import KernelTraffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: Callable


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    workload: dict
    kernels: Dict[str, KernelTraffic]
    end_to_end: List[Metric]
    per_layer: List[Metric]


def load_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def reader(name: str) -> Callable:
    """``read`` of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    module_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if module_spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read


def _metrics(entries: List[dict], cell: str) -> List[Metric]:
    return [Metric(m["name"], m["unit"], reader(m["name"]))
            for m in entries if cell in m.get("workloads", (cell,))]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    doc = load_json(root / "BENCHMARK.json")
    entry = next((w for w in doc["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in doc["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({known})")
    config_entry = next(c for c in doc["configs"]
                        if c["name"] == entry["config"])
    config = load_json(root / config_entry["file"])
    workload = load_json(BENCH / "workloads" / f"{name}.json")
    kernels = {
        k: KernelTraffic.from_json(k, load_json(BENCH / "kernels" / f"{k}.json"))
        for k in workload["kernels"]}
    return Cell(name=name, chips=int(entry["chips"]), config=config,
                workload=workload, kernels=kernels,
                end_to_end=_metrics(doc["end_to_end"], name),
                per_layer=_metrics(doc["per_layer"], name))
