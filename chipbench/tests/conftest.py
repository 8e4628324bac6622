import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from chipbench.harness.traffic import size_cycle  # noqa: E402

TINY_SIZES = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=160,
                  num_attention_heads=8, num_key_value_heads=2, vocab_size=256)


def tiny_root(tmp: Path, seconds_rate: float = 10.0) -> Path:
    """A benchmark rooted at ``tmp`` with one cell, ``tiny-gqa.burst``,
    whose configuration, mix, cell and metrics are files only: a tiny
    stage of the deepseek-coder configuration under the prema_burst mix
    at small prompts."""
    base = tmp / "chipbench"
    for d in ("configs", "traffic", "cells"):
        (base / d).mkdir(parents=True, exist_ok=True)
    shutil.copytree(ROOT / "chipbench" / "metrics", base / "metrics",
                    dirs_exist_ok=True)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny-gqa", "source": "test",
                         "file": "chipbench/configs/tiny-gqa.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [{"name": "tiny-gqa.burst", "config": "tiny-gqa",
                           "traffic": "burst", "chips": 1, "why": "test"}]
    for m in bench["per_layer"]:
        m["workloads"] = ["tiny-gqa.burst"]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    spec = json.loads((ROOT / "chipbench/configs/deepseek-coder-33b-pp8.json")
                      .read_text())
    spec.update(TINY_SIZES)
    (base / "configs/tiny-gqa.json").write_text(json.dumps(spec))
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
            "correct": {"max_logit_gap": 0.05, "sample_tokens": 60,
                        "sample_requests": 6}}
    (base / "cells/tiny-gqa.burst.json").write_text(json.dumps(cell))
    return tmp


@pytest.fixture
def tiny(tmp_path):
    return tiny_root(tmp_path)
