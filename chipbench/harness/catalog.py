"""Everything of a cell, found by name in files of its own.

``BENCHMARK.json`` names each cell's configuration and traffic; the
harness reads ``chipbench/configs/<config>.json``,
``chipbench/traffic/<traffic>.json``, ``chipbench/cells/<cell>.json`` (the
cell's fixed rates, isolated times and limits) and, for each per-layer
metric that lists the cell, ``chipbench/metrics/<metric>.py``.  The
configuration's ``reference`` key names its model family: its statement
and counts in ``chipbench/families/<name>.py`` and its plain reference in
``chipbench/reference/<name>.py``.  A later change adds a cell, or a
family, by adding files and entries; it edits none.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List

BENCH_DIR = "chipbench"
# The benchmark root a configuration was read from, kept in the loaded
# configuration so that its family and reference are found beside it; a
# configuration without it (one written out in a test) is this checkout's.
ROOT_KEY = "_root"
CHECKOUT = Path(__file__).resolve().parents[2]


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
        spec=dict(_json(base / "configs" / f"{w['config']}.json"),
                  **{ROOT_KEY: str(root)}),
        mix=_json(base / "traffic" / f"{w['traffic']}.json"),
        params=_json(base / "cells" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if _reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if _reports(m, name)])


@functools.lru_cache(maxsize=None)
def _load(path: str) -> ModuleType:
    """The module in the file ``path``, executed once per process: a
    family's or reference's jitted functions keep their compiled
    programs."""
    name = "chipbench_" + "_".join(Path(path).parts[-2:]).replace(
        ".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(root: Path, metric: str) -> Callable:
    """The ``read(run)`` function of ``chipbench/metrics/<metric>.py``."""
    return _load(str(root / BENCH_DIR / "metrics" / f"{metric}.py")).read


def _named(spec: Dict, kind: str) -> ModuleType:
    if "reference" not in spec:
        raise KeyError("the configuration names no family: it needs a "
                       "'reference' key")
    root = Path(spec.get(ROOT_KEY, CHECKOUT))
    return _load(str(root / BENCH_DIR / kind / f"{spec['reference']}.py"))


def family(spec: Dict) -> ModuleType:
    """The configuration's family, ``chipbench/families/<reference>.py``:
    its statement as the program's ``ArchConfig``, its weights' layout and
    its operation and byte counts."""
    return _named(spec, "families")


def reference(spec: Dict) -> ModuleType:
    """The configuration's plain reference,
    ``chipbench/reference/<reference>.py``."""
    return _named(spec, "reference")
