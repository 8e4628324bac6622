"""Jitted wrapper for flash-decoding attention."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.kernel import decode_attention_raw


@functools.partial(jax.jit, static_argnames=("bt", "interpret"))
def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, bt: int = 512,
                     interpret: bool = False) -> jax.Array:
    """q: (B,Hq,D) one token per sequence; k,v: (B,Hkv,T,D); pos scalar.
    Returns (B,Hq,D)."""
    b, hq, d = q.shape
    _, hkv, t, _ = k.shape
    g = hq // hkv
    qg = q.reshape(b, hkv, g, d)
    tp = (-t) % bt
    if tp:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, tp), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, tp), (0, 0)))
    out = decode_attention_raw(qg, k, v, pos, bt=bt, interpret=interpret)
    return out.reshape(b, hq, d)
