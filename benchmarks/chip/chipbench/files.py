"""Finds a cell's files by the names in BENCHMARK.json.

Every configuration, traffic mix, driver, reference and metric reader sits
in a file of its own; nothing here names one, so a later cell, config or
metric is added as files and entries alone.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import List

__all__ = ["BENCH_DIR", "ROOT", "Cell", "load_benchmark", "resolve_cell",
           "load_module", "metric_applies"]

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as fh:
        return json.load(fh)


def load_module(path: pathlib.Path):
    """Import a benchmark file by path (its name may hold dots)."""
    name = "chipbench_file_" + "_".join(
        path.relative_to(BENCH_DIR).with_suffix("").parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_applies(metric: dict, cell: str) -> bool:
    """A per-layer metric is read in the cells its ``workloads`` names."""
    if "workloads" not in metric:
        raise ValueError(f"per-layer metric {metric['name']!r} names no workloads")
    return cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict            # the configuration file's contents
    config_entry: dict      # its entry in BENCHMARK.json
    traffic: dict           # the traffic file's contents
    end_to_end: List[dict]  # entries that this cell reports
    per_layer: List[dict]

    @property
    def driver_path(self) -> pathlib.Path:
        return BENCH_DIR / "drivers" / f"{self.traffic['driver']}.py"

    @property
    def reference_path(self) -> pathlib.Path:
        return BENCH_DIR / "references" / f"{self.config['reference']}.py"

    def metric_path(self, name: str) -> pathlib.Path:
        return BENCH_DIR / "metrics" / f"{name}.py"


def resolve_cell(bench: dict, name: str, root: pathlib.Path = ROOT) -> Cell:
    """The workload ``name`` with its configuration, traffic and metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / cfg_entry["file"]) as fh:
        config = json.load(fh)
    with open(BENCH_DIR / "traffic" / f"{w['traffic']}.json") as fh:
        traffic = json.load(fh)
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    layer = [m for m in bench["per_layer"] if metric_applies(m, name)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                config_entry=cfg_entry, traffic=traffic, end_to_end=e2e,
                per_layer=layer)

