"""A configuration file, as the program runs it, and its weights.

A configuration is a JSON file under ``chipbench/configs/`` in the key
names of the model's published ``config.json``, holding the sizes as run.
Its ``reference`` key names its family, ``chipbench/families/<name>.py``
(found by ``catalog.family``), which gives:

* ``arch_config(name, spec)``: the program's ``ArchConfig``, refusing what
  the program cannot state;
* ``weight_shapes(spec)``: leaf path -> ``(shape, init)`` in the program's
  parameter layout, layers stacked on the leading axis (``init`` as
  :func:`_leaf` reads it);
* ``empty_nodes(spec)``: paths of the layout's nodes without leaves;
* the counts that ``counts.py`` forwards to, and ``TINY`` and
  ``TINY_LIMIT``, its small sizes and their limit for the CPU tests.

Nothing here imports a widths module from ``src/``.  :func:`make_weights`
builds the weights from the seed on the device, in one jitted call, in the
dtype they are served in and in the program's parameter layout.  The same
weights are what the benchmark's plain reference reads, so neither side
takes a number the other made.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import catalog


def build_model(name: str, spec: Dict):
    """``(Model, ArchConfig)`` through the program's registry, the config
    stated by the configuration's family."""
    from repro.models.registry import build
    cfg = catalog.family(spec).arch_config(name, spec)
    return build(cfg), cfg


def _nest(flat: Dict, empty) -> Dict:
    """The pytree of ``flat``'s leaves by path, with an empty node at each
    path in ``empty``: the program keeps a norm without parameters as a
    node with no leaves."""
    tree: Dict = {}
    for path in empty:
        node = tree
        for p in path.split("/"):
            node = node.setdefault(p, {})
    for path, leaf in flat.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


def tree_layout(spec: Dict) -> Dict:
    """The parameter pytree with every leaf a ShapeDtypeStruct."""
    fam = catalog.family(spec)
    dtype = jnp.dtype(spec["serve_dtype"])
    return _nest({k: jax.ShapeDtypeStruct(s, dtype)
                  for k, (s, _) in fam.weight_shapes(spec).items()},
                 fam.empty_nodes(spec))


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


def _leaf(init, salt: int, words, shape, dtype) -> jax.Array:
    """One leaf by the initialisation its family names for it: ``"ones"``;
    ``("uniform", fan_in)``, uniform with standard deviation
    ``fan_in**-0.5``; or a function of the leaf's own uniform [0, 1)
    draws, for a leaf that needs other values."""
    if init == "ones":
        return jnp.ones(shape, dtype)
    u = _uniform(salt, words, shape)
    if callable(init):
        return init(u).astype(dtype)
    kind, fan_in = init
    if kind != "uniform":
        raise ValueError(f"unknown initialisation {init!r}")
    half_width = np.sqrt(3.0 / fan_in)
    return ((u * 2.0 - 1.0) * half_width).astype(dtype)


def make_weights(spec: Dict, seed: int):
    """All weights on the default device in one jitted call, each leaf as
    its family's leaf table says, in the served dtype.  Leaf ``i`` in the
    sorted order of paths draws with salt ``i + 1``.  The seed is an
    argument, so one compile serves every seed."""
    fam = catalog.family(spec)
    shapes = fam.weight_shapes(spec)
    empty = fam.empty_nodes(spec)
    dtype = jnp.dtype(spec["serve_dtype"])

    def build(words):
        flat = {path: _leaf(init, i + 1, words, shape, dtype)
                for i, (path, (shape, init)) in enumerate(sorted(shapes.items()))}
        return _nest(flat, empty)

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
