import json
import shutil
import sys
from pathlib import Path
from typing import Dict

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench.harness import catalog  # noqa: E402
from chipbench.harness.traffic import size_cycle  # noqa: E402

# Every configuration file of the benchmark; a test parametrised over them
# takes a configuration added as a file with no test edited.
CONFIGS = sorted(p.stem for p in (ROOT / "chipbench/configs").glob("*.json"))
TINY_CELL = "tiny.burst"


def config(name: str) -> Dict:
    """The configuration file ``name`` of this checkout."""
    return json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())


def tiny_spec(spec: Dict, **over) -> Dict:
    """``spec`` at its family's small sizes, ``TINY``."""
    return dict(spec, **catalog.family(spec).TINY, **over)


def tiny_root(tmp: Path, spec: Dict, seconds_rate: float = 10.0) -> Path:
    """A benchmark rooted at ``tmp`` with one cell, ``tiny.burst``, whose
    configuration, mix, cell, family, reference and metrics are files
    only: ``spec`` at its family's small sizes under the prema_burst mix at
    small prompts.  The families and references are this checkout's, beside
    any that a test wrote under ``tmp`` before."""
    base = tmp / "chipbench"
    for d in ("configs", "traffic", "cells"):
        (base / d).mkdir(parents=True, exist_ok=True)
    for d in ("metrics", "families", "reference"):
        shutil.copytree(ROOT / "chipbench" / d, base / d, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "chipbench/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": TINY_CELL, "config": "tiny",
                           "traffic": "burst", "chips": 1, "why": "test"}]
    for m in bench["per_layer"]:
        m["workloads"] = [TINY_CELL]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    fam = catalog.family(dict(spec, **{catalog.ROOT_KEY: str(tmp)}))
    (base / "configs/tiny.json").write_text(json.dumps(dict(spec, **fam.TINY)))
    mix = json.loads((ROOT / "chipbench/traffic/prema_burst.json").read_text())
    mix["max_context"] = 160
    hi, lo = (t["lengths"] for t in mix["tenants"])
    hi["prompt"].update(median=24, min=8)
    hi["output"].update(median=3)
    lo["prompt"].update(median=80, min=8)
    lo["output"].update(median=8)
    mix["tenants"][0]["size_cycle"] = 6
    mix["tenants"][1]["size_cycle"] = 4
    (base / "traffic/burst.json").write_text(json.dumps(mix))
    cycle = size_cycle(mix["tenants"][0], mix["max_context"],
                       np.random.default_rng([mix["shape_seed"], 0]))
    cell = {"rates_per_s": {"interactive": seconds_rate}, "sla_scale": 8.0,
            "isolated_s": {f"{p}x{n}": 0.01 + 0.003 * n for p, n in cycle},
            "correct": {"max_logit_gap": fam.TINY_LIMIT, "sample_tokens": 200,
                        "sample_requests": 12}}
    (base / f"cells/{TINY_CELL}.json").write_text(json.dumps(cell))
    return tmp


@pytest.fixture(params=CONFIGS)
def tiny(tmp_path, request):
    return tiny_root(tmp_path, config(request.param))
