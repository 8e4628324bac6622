"""Compiles for a described TPU v5e, with no chip attached.

The Pallas kernels at olmo-1b widths and the executor's jitted steps at the
full width of olmo-1b (bf16 weights passed as arguments) go through the
chip's own compiler, which refuses what interpret mode on the CPU accepts:
unaligned tiles, too much fast memory, a program that does not fit.  Nothing
runs, so these say nothing about results or times.

Every test that describes the chip lives in this file, and the topology is
described only inside its fixtures: only one process at a time may load the
TPU library, so describing it while a module is imported would break the
other test workers.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import decode_attention_raw
from repro.kernels.flash_attention.kernel import flash_attention_raw
from repro.kernels.preemptible_matmul.kernel import matmul_resumable_raw
from repro.models import get_model, transformer
from repro.serving import executor


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def olmo():
    return get_model("olmo-1b")


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on_chip(tree, sharding):
    return jax.tree.map(lambda s: _sds(s.shape, s.dtype, sharding), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_topology_is_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"


@pytest.mark.parametrize("g", [1, 4])
def test_decode_attention_kernel_compiles(one_chip, g):
    hkv, d, t = 16, 128, 4096              # olmo-1b heads, 4k-token cache
    q = _sds((1, hkv, g, d), jnp.bfloat16, one_chip)
    kv = _sds((1, hkv, t, d), jnp.bfloat16, one_chip)
    pos = _sds((), jnp.int32, one_chip)
    hlo = _compile(functools.partial(decode_attention_raw, bt=512),
                   q, kv, kv, pos)
    assert "tpu_custom_call" in hlo


def test_flash_attention_kernel_compiles(one_chip):
    qkv = _sds((1, 16, 2048, 128), jnp.bfloat16, one_chip)
    hlo = _compile(functools.partial(flash_attention_raw, bq=256, bt=256),
                   qkv, qkv, qkv)
    assert "tpu_custom_call" in hlo


def test_preemptible_matmul_kernel_compiles(one_chip):
    m, k, n = 512, 2048, 8192
    x = _sds((m, k), jnp.bfloat16, one_chip)
    y = _sds((k, n), jnp.bfloat16, one_chip)
    acc = _sds((m, n), jnp.float32, one_chip)
    hlo = _compile(functools.partial(matmul_resumable_raw, k_start=0,
                                     k_end=k // 128), x, y, acc)
    assert "tpu_custom_call" in hlo


def _olmo_params(olmo, sharding):
    init = functools.partial(olmo.init_params,
                             dtype=jnp.dtype(olmo.cfg.dtype))
    return _on_chip(jax.eval_shape(init, jax.random.PRNGKey(0)), sharding)


def test_executor_decode_compiles_full_width(olmo, one_chip):
    cfg = olmo.cfg
    params = _olmo_params(olmo, one_chip)
    cache = _on_chip(transformer.cache_spec(cfg, 1, 2048), one_chip)
    tok = _sds((1, 1), jnp.int32, one_chip)
    pos = _sds((), jnp.int32, one_chip)
    lowered = executor._decode.lower(params, cache, tok, pos, cfg=cfg)
    # every weight is an argument of the program, none a constant in it
    main = re.search(r"func\.func public @main\((.*?)\)\s*->",
                     lowered.as_text(), re.S).group(1)
    n_leaves = len(jax.tree.leaves(params)) + len(jax.tree.leaves(cache))
    assert main.count("%arg") == n_leaves + 2
    mem = lowered.compile().memory_analysis()
    weights = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= weights
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < 16e9


def test_executor_prefill_period_compiles_full_width(olmo, one_chip):
    cfg = olmo.cfg
    params = _olmo_params(olmo, one_chip)
    one_period = jax.tree.map(
        lambda s: _sds(s.shape[1:], s.dtype, one_chip), params["slots"])
    h = _sds((1, 256, cfg.d_model), jnp.dtype(cfg.dtype), one_chip)
    compiled = executor._period_prefill.lower(
        one_period, h, None, cfg=cfg).compile()
    layer = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(one_period))
    assert compiled.memory_analysis().argument_size_in_bytes >= layer
