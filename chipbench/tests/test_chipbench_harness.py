"""The harness end to end on the CPU at a tiny size: a cell found from
files alone, metrics read by name, the refusal to run without a chip, and
``correct`` coming out false when the served tokens are broken."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench import run
from chipbench.harness import catalog, peaks
from chipbench.harness.serve import Record, Window
from chipbench.harness.traffic import Request
from chipbench.tests.conftest import ROOT, TINY_CELL

V5E = peaks.PEAKS["TPU v5 lite"]


def _run(root, seed=2 ** 33 + 11):
    return run.run_cell(root, TINY_CELL, seed, 1.5, False,
                        require_tpu=False, peaks=V5E, log=lambda m: None)


def test_cell_added_as_files_runs_and_is_correct(tiny):
    res = _run(tiny)
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"hi_tokens_per_s", "batch_tokens_per_s",
                                   "setup_s"}
    assert list(res)[-1] == "checks"
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert res["device"]["platform"] == "cpu"


def test_metric_added_as_a_file_is_read_by_name(tiny):
    (tiny / "chipbench/metrics/engine.sent_total.py").write_text(
        "def read(run):\n    return float(len(run.window.records))\n")
    bench = json.loads((tiny / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "engine.sent_total", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "ServingEngine arrival intake",
        "moves": "hi_latency_p90_s", "workloads": [TINY_CELL]})
    (tiny / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = catalog.find(tiny, TINY_CELL)
    plen, out = (int(v) for v in sorted(cell.params["isolated_s"])[0].split("x"))
    reqs = [Request(i, "t", 9 if i < 2 else 1, np.zeros((1, plen), np.int32),
                    out, due=float(i)) for i in range(3)]
    recs = {r.rid: Record(r, sent=10.0 + i, submit=10.5 + i, complete=11.0 + i,
                          tokens=np.zeros((1, out), np.int32))
            for i, r in enumerate(reqs)}
    data = run.RunData(window=Window(10.0, 20.0, recs, False), spec=cell.spec,
                       cell=cell.params, mix=cell.mix, peaks=V5E, n_chips=1,
                       hi_priority=9,
                       compiles=[(15.0, "x"), (25.0, "x")])
    got = run.layer_metrics(tiny, cell, data)
    assert got["engine.sent_total"] == {"value": 3.0, "unit": "count"}
    assert got["models.compiles_in_window"]["value"] == 1.0
    assert got["engine.ingest_lag_p90_ms"]["value"] == pytest.approx(500.0)
    assert got["hi_latency_p50_s"]["value"] == pytest.approx(1.0)
    # readers of the device trace find nothing to read without one
    assert "device.idle_share" not in got and "decode_roofline" not in got


def test_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload",
                        "olmo-1b.prema_burst", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU chip" in p.stderr


def test_token_altered_where_produced_is_not_correct(tiny, monkeypatch):
    from repro.serving.executor import PreemptibleExecutor
    step = PreemptibleExecutor.step_decode

    def altered(self, st):
        st = step(self, st)
        st.tokens_out[-1] = (st.tokens_out[-1] + 1) % self.cfg.vocab_size
        return st
    monkeypatch.setattr(PreemptibleExecutor, "step_decode", altered)
    res = _run(tiny)
    assert res["correct"] is False
    gap = res["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]
