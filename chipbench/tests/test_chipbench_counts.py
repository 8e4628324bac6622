"""The benchmark's FLOP and byte counts against XLA's ``cost_analysis`` of
the program's own jitted steps, at small widths on the CPU.

The counts are what the algorithm needs, so they lie under what XLA
counts for the compiled program: by the elementwise work they leave out,
and in prefill by what the family says the program computes beyond the
need (for the dense decoder the masked half of the score matrix).  Each
configuration file is taken at its family's ``TINY`` sizes, compiled with
float32 weights and one period of layers: XLA's CPU backend counts a
bfloat16 product's upcasts as FLOPs, and a scan's body once whatever its
trip count.
"""
import jax
import jax.numpy as jnp
import pytest

from chipbench.harness import catalog, counts, model
from chipbench.tests.conftest import CONFIGS, config, tiny_spec


def _spec(name):
    spec = tiny_spec(config(name), serve_dtype="float32")
    period = len(catalog.family(spec).arch_config(name, spec).block_pattern)
    return dict(spec, num_hidden_layers=period)


def _cost(fn, *args, **kw):
    c = jax.jit(fn, static_argnames=tuple(kw)).lower(*args, **kw).compile()
    c = c.cost_analysis()
    return c["flops"], c["bytes accessed"]


@pytest.fixture(scope="module", params=CONFIGS)
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
    beyond = catalog.family(spec).prefill_beyond_need(spec, seq)
    assert f <= xf <= 1.05 * (f + beyond), (f, beyond, xf)
    assert b <= xb, (b, xb)


@pytest.mark.parametrize("name", CONFIGS)
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
    olmo, ds = config("olmo-1b"), config("deepseek-coder-33b-pp8")
    assert counts.weight_bytes(olmo) == pytest.approx(2.354e9, rel=1e-3)
    assert counts.weight_bytes(ds) == pytest.approx(9.410e9, rel=1e-3)
    assert counts.kv_bytes_per_token(olmo) == 128 * 1024
    assert counts.kv_bytes_per_token(ds) == 32 * 1024
