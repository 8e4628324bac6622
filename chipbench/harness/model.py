"""A configuration file, as the program runs it, and its weights.

A configuration is a JSON file under ``chipbench/configs/`` in the key
names of the model's published ``config.json``, holding the sizes as run.
:func:`arch_config` states it as the program's ``ArchConfig``; the file
never imports a widths module from ``src/``.

:func:`make_weights` builds the weights from the seed on the device, in one
jitted call, in the dtype they are served in and in the program's parameter
layout.  The same weights are what the benchmark's plain reference reads,
so neither side takes a number the other made.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# model_type of the published config -> the norm it uses.  OLMo-1B uses a
# LayerNorm with no scale or bias (arXiv:2402.00838, section 2.1); the
# llama family an RMSNorm with a scale.
NORMS = {"olmo": "layernorm_np", "llama": "rmsnorm"}


def arch_config(name: str, spec: Dict):
    """The program's ``ArchConfig`` for a configuration file."""
    from repro.configs import ArchConfig
    if spec["hidden_act"] != "silu":
        raise ValueError(f"{name}: hidden_act {spec['hidden_act']!r}; "
                         "only the SwiGLU MLP (silu) is stated")
    if spec.get("rope_scaling") is not None:
        raise ValueError(f"{name}: rope_scaling is not supported by the "
                         "program; list the key in 'reduced' and set null")
    if spec.get("attention_bias"):
        raise ValueError(f"{name}: attention_bias is not supported here")
    return ArchConfig(
        name=name,
        family="dense",
        n_layers=int(spec["num_hidden_layers"]),
        d_model=int(spec["hidden_size"]),
        n_heads=int(spec["num_attention_heads"]),
        n_kv_heads=int(spec["num_key_value_heads"]),
        d_ff=int(spec["intermediate_size"]),
        vocab_size=int(spec["vocab_size"]),
        block_pattern=(("attn", "mlp"),),
        norm=NORMS[spec["model_type"]],
        mlp_act="silu",
        rope_theta=float(spec["rope_theta"]),
        tie_embeddings=bool(spec["tie_word_embeddings"]),
        dtype=spec["serve_dtype"],
    )


def build_model(name: str, spec: Dict):
    """``(Model, ArchConfig)`` through the program's registry."""
    from repro.models.registry import build
    cfg = arch_config(name, spec)
    return build(cfg), cfg


def weight_shapes(spec: Dict) -> Dict:
    """Leaf name -> (shape, fan_in or None for ones) in the program's
    parameter layout, layers stacked on the leading axis."""
    L = int(spec["num_hidden_layers"])
    d = int(spec["hidden_size"])
    hq = int(spec["num_attention_heads"])
    hkv = int(spec["num_key_value_heads"])
    dh = d // hq
    f = int(spec["intermediate_size"])
    v = int(spec["vocab_size"])
    out = {
        "embed/table": ((v, d), d),
        "slots/slot0/mixer/wq": ((L, d, hq, dh), d),
        "slots/slot0/mixer/wk": ((L, d, hkv, dh), d),
        "slots/slot0/mixer/wv": ((L, d, hkv, dh), d),
        "slots/slot0/mixer/wo": ((L, hq, dh, d), hq * dh),
        "slots/slot0/ffn/w_in": ((L, d, f), d),
        "slots/slot0/ffn/w_gate": ((L, d, f), d),
        "slots/slot0/ffn/w_out": ((L, f, d), f),
    }
    if NORMS[spec["model_type"]] == "rmsnorm":
        out["slots/slot0/norm1/scale"] = ((L, d), None)
        out["slots/slot0/norm2/scale"] = ((L, d), None)
        out["final_norm/scale"] = ((d,), None)
    if not spec["tie_word_embeddings"]:
        out["lm_head/w"] = ((d, v), d)
    return out


def _nest(flat: Dict) -> Dict:
    tree: Dict = {}
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def _with_norms(tree: Dict) -> Dict:
    """Add the empty norm dicts of a non-parametric LayerNorm, which the
    program's layout keeps as leaves-free nodes."""
    slot = tree["slots"]["slot0"]
    slot.setdefault("norm1", {})
    slot.setdefault("norm2", {})
    tree.setdefault("final_norm", {})
    return tree


def tree_layout(spec: Dict) -> Dict:
    """The parameter pytree with every leaf a ShapeDtypeStruct."""
    dtype = jnp.dtype(spec["serve_dtype"])
    return _with_norms(_nest({k: jax.ShapeDtypeStruct(s, dtype)
                              for k, (s, _) in weight_shapes(spec).items()}))


def _uniform(salt: int, seed_words, shape) -> jax.Array:
    """Uniform [0, 1) floats from a counter hash of (leaf, seed, index):
    a murmur3 finaliser over a 32-bit counter, mixed with both 32-bit
    words of the seed, so every seed up to 2**64 gives its own weights."""
    n = int(np.prod(shape))
    x = jax.lax.iota(jnp.uint32, n)
    x = x * jnp.uint32(0x9E3779B1) + jnp.uint32(salt * 0x85EBCA77 & 0xFFFFFFFF)
    for word in (seed_words[0], seed_words[1]):
        x = x ^ word
        x = x ^ (x >> 16)
        x = x * jnp.uint32(0x85EBCA6B)
        x = x ^ (x >> 13)
        x = x * jnp.uint32(0xC2B2AE35)
        x = x ^ (x >> 16)
    u = (x >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)
    return u.reshape(shape)


def seed_words(seed: int) -> np.ndarray:
    """The seed as two unsigned 32-bit words (low, high)."""
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return np.asarray([seed & 0xFFFFFFFF, seed >> 32], np.uint32)


def make_weights(spec: Dict, seed: int):
    """All weights on the default device in one jitted call: uniform with
    standard deviation fan_in**-0.5 (norm scales are ones), in the served
    dtype.  The seed is an argument, so one compile serves every seed."""
    shapes = weight_shapes(spec)
    dtype = jnp.dtype(spec["serve_dtype"])

    def build(words):
        flat = {}
        for i, (path, (shape, fan_in)) in enumerate(sorted(shapes.items())):
            if fan_in is None:
                flat[path] = jnp.ones(shape, dtype)
                continue
            half_width = np.sqrt(3.0 / fan_in)
            u = _uniform(i + 1, words, shape)
            flat[path] = ((u * 2.0 - 1.0) * half_width).astype(dtype)
        return _with_norms(_nest(flat))

    return jax.block_until_ready(jax.jit(build)(jnp.asarray(seed_words(seed))))


def check_layout(model, spec: Dict) -> None:
    """Fail loudly if the program's parameter layout has moved away from
    the one the benchmark builds."""
    want = jax.eval_shape(lambda k: model.init_params(
        k, dtype=jnp.dtype(spec["serve_dtype"])), jax.random.PRNGKey(0))
    got = tree_layout(spec)
    ws, gs = jax.tree.structure(want), jax.tree.structure(got)
    if ws != gs:
        raise ValueError(f"parameter layout differs from the program's: "
                         f"{gs} vs {ws}")
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        if (w.shape, w.dtype) != (g.shape, g.dtype):
            raise ValueError(f"leaf {g.shape} {g.dtype} vs the program's "
                             f"{w.shape} {w.dtype}")
