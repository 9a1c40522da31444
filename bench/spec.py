"""Resolve a cell of ``BENCHMARK.json`` to its files by name.

A cell ``<config>.<traffic>`` names a configuration file
(``bench/configs/<config>.json``), a traffic mix
(``bench/traffic/<traffic>.json``) and the limits its correctness check
holds (``bench/limits/<cell>.json``); each per-layer metric is a reader
``bench/metrics/<name>.py`` with one function ``read(ctx)``. Adding a
cell, a mix or a metric adds files and entries; no code here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def _applies(metric: Dict[str, Any], cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def resolve(workload: str, root: Path = ROOT) -> Cell:
    spec = load_json(root / "BENCHMARK.json")
    entry = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if entry is None:
        known = [w["name"] for w in spec["workloads"]]
        raise KeyError(f"unknown workload {workload!r}; known: {known}")
    bench = root / "bench"
    config = next(c for c in spec["configs"] if c["name"] == entry["config"])
    e2e = [m for m in spec["end_to_end"] if _applies(m, workload, [])]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in spec["per_layer"] if _applies(m, workload, names)]
    return Cell(name=workload, chips=int(entry["chips"]),
                config=load_json(root / config["file"]),
                traffic=load_json(bench / "traffic" / f"{entry['traffic']}.json"),
                limits=load_json(bench / "limits" / f"{workload}.json"),
                end_to_end=e2e, per_layer=per_layer)


def reader(metric: str, root: Path = ROOT) -> Callable:
    """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read
