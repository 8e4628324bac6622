"""Everything of a cell, found by name in files of its own.

``BENCHMARK.json`` names each cell's configuration and traffic; the
harness reads ``chipbench/configs/<config>.json``,
``chipbench/traffic/<traffic>.json``, ``chipbench/cells/<cell>.json`` (the
cell's fixed rates, isolated times and limits) and, for each per-layer
metric that lists the cell, ``chipbench/metrics/<metric>.py``.  A later
change adds a cell by adding files and entries; it edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

BENCH_DIR = "chipbench"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    spec: Dict            # the configuration file
    mix: Dict             # the traffic file
    params: Dict          # the cell's own file
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find(root: Path, name: str) -> Cell:
    """The cell ``name`` of the benchmark rooted at ``root``."""
    bench = _json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    base = root / BENCH_DIR
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        spec=_json(base / "configs" / f"{w['config']}.json"),
        mix=_json(base / "traffic" / f"{w['traffic']}.json"),
        params=_json(base / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


def reader(root: Path, metric: str) -> Callable:
    """The ``read(run)`` function of ``chipbench/metrics/<metric>.py``."""
    path = root / BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
