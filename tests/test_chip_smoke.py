"""CPU rehearsal of ``chip_smoke.py``: its serving and reference checks on
the reduced olmo-1b config, the schedule it drives at full width (in virtual
time), and its refusal to run without a TPU."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from repro.hw import TPU_V5E
from repro.models import get_model
from repro.serving import EngineConfig, ServingEngine

ROOT = Path(__file__).resolve().parents[1]

# Model execution (real JAX compute): excluded from `make test-fast`.
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def tiny():
    model = get_model("olmo-1b", tiny=True)
    return model, chip_smoke.build_params(model)


def test_serve_check_passes_on_tiny_config(tiny):
    model, params = tiny
    out = chip_smoke.serve_check(model, params, TPU_V5E)
    n_req = sum(n for n, _ in chip_smoke.MIX.values())
    assert out["requests"] == n_req
    assert out["preemptions"] >= 1


def test_reference_check_passes_on_tiny_config(tiny):
    model, params = tiny
    prompt = chip_smoke.make_requests(model, TPU_V5E)[-1].prompt
    out = chip_smoke.reference_check(model, params, prompt)
    assert 0.0 < out["rel_l2"] <= chip_smoke.REF_TOL


def test_reference_check_raises_above_tolerance(tiny):
    model, params = tiny
    prompt = chip_smoke.make_requests(model, TPU_V5E)[-1].prompt
    with pytest.raises(chip_smoke.SmokeFailure, match="relative L2"):
        chip_smoke.reference_check(model, params, prompt, tol=1e-6)


def test_full_width_schedule_preempts():
    """At olmo-1b's full width the engine's predicted times place every
    priority-9 arrival inside a running priority-1 request (virtual time,
    no tensors)."""
    model = get_model("olmo-1b")
    engine = ServingEngine({model.cfg.name: (model, None)}, cfg=EngineConfig(
        hw=TPU_V5E, policy="prema", mechanism="dynamic", execute=False))
    results = engine.run(chip_smoke.make_requests(model, TPU_V5E))
    assert len(results) == sum(n for n, _ in chip_smoke.MIX.values())
    low = [r for r in results if r.priority == 1]
    assert sum(r.n_preemptions > 0 for r in low) == len(low)


def test_refuses_to_run_without_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "needs a TPU" in proc.stderr
