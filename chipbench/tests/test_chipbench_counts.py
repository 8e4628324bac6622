"""The benchmark's FLOP and byte counts against XLA's ``cost_analysis`` of
the program's own jitted steps, at small widths on the CPU.

The counts are what the algorithm needs, so they lie under what XLA
counts for the compiled program: by the elementwise work they leave out,
and in prefill by the masked half of the score matrix, which the program
computes and the count does not.  The steps are compiled with float32
weights and one layer: XLA's CPU backend counts a bfloat16 product's
upcasts as FLOPs, and a scan's body once whatever its trip count.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from chipbench.harness import counts, model
from chipbench.tests.conftest import ROOT

SIZES = dict(num_hidden_layers=1, hidden_size=256, intermediate_size=512,
             num_attention_heads=8, num_key_value_heads=2, vocab_size=512,
             serve_dtype="float32")


def _spec(name):
    spec = json.loads((ROOT / f"chipbench/configs/{name}.json").read_text())
    spec.update(SIZES)
    return spec


def _cost(fn, *args, **kw):
    c = jax.jit(fn, static_argnames=tuple(kw)).lower(*args, **kw).compile()
    c = c.cost_analysis()
    return c["flops"], c["bytes accessed"]


@pytest.fixture(scope="module", params=["olmo-1b", "deepseek-coder-33b-pp8"])
def built(request):
    spec = _spec(request.param)
    m, cfg = model.build_model(request.param, spec)
    params = jax.eval_shape(lambda: model.make_weights(spec, 0))
    return spec, cfg, params


@pytest.mark.parametrize("pos", [63, 255])
def test_decode_counts_under_xla(built, pos):
    from repro.models import transformer
    from repro.serving import executor
    spec, cfg, params = built
    cache = transformer.cache_spec(cfg, 1, pos + 1, dtype=jnp.float32)
    tok = jax.ShapeDtypeStruct((1, 1), jnp.int32)
    p = jax.ShapeDtypeStruct((), jnp.int32)
    xf, xb = _cost(executor._decode.__wrapped__, params, cache, tok, p, cfg=cfg)
    f, b = counts.decode_step(spec, pos)
    assert f <= xf <= 1.05 * f, (f, xf)
    assert b <= xb, (b, xb)


@pytest.mark.parametrize("seq", [64, 256])
def test_prefill_counts_under_xla(built, seq):
    from repro.serving import executor
    spec, cfg, params = built
    slots = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                         params["slots"])
    h = jax.ShapeDtypeStruct((1, seq, cfg.d_model), jnp.float32)
    xf, xb = _cost(executor._period_prefill.__wrapped__, slots, h, None,
                   cfg=cfg)
    f, b = counts.prefill_period(spec, seq)
    masked = 2 * 2 * cfg.n_heads * cfg.d_head * seq * (seq - 1) // 2
    assert f <= xf <= 1.05 * (f + masked), (f, masked, xf)
    assert b <= xb, (b, xb)


@pytest.mark.parametrize("name", ["olmo-1b", "deepseek-coder-33b-pp8"])
def test_weight_bytes_match_the_weights(name):
    spec = _spec(name)
    params = jax.eval_shape(lambda: model.make_weights(spec, 0))
    assert counts.weight_bytes(spec) == sum(
        a.size * a.dtype.itemsize for a in jax.tree.leaves(params))


def test_least_time_names_its_bound():
    peaks = {"flops_bf16": 100.0, "hbm_bytes_per_s": 10.0}
    assert counts.least_time(1000.0, 10.0, peaks) == (10.0, "compute")
    assert counts.least_time(10.0, 1000.0, peaks) == (100.0, "memory")


def test_full_width_weight_bytes():
    """The published sizes: olmo-1b 2.36 GB, the deepseek stage 9.41 GB."""
    olmo = json.loads((ROOT / "chipbench/configs/olmo-1b.json").read_text())
    ds = json.loads((ROOT / "chipbench/configs/deepseek-coder-33b-pp8.json")
                    .read_text())
    assert counts.weight_bytes(olmo) == pytest.approx(2.354e9, rel=1e-3)
    assert counts.weight_bytes(ds) == pytest.approx(9.410e9, rel=1e-3)
    assert counts.kv_bytes_per_token(olmo) == 128 * 1024
    assert counts.kv_bytes_per_token(ds) == 32 * 1024
