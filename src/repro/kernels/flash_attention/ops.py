"""Jitted wrapper for the flash-attention kernel (padding + layout)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_raw


@functools.partial(jax.jit,
                   static_argnames=("causal", "bq", "bt", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, bq: int = 128, bt: int = 128,
                    interpret: bool = False) -> jax.Array:
    """q: (B,Hq,S,D); k,v: (B,Hkv,T,D) → (B,Hq,S,D).

    Pads S and T up to block multiples; padded keys are masked inside the
    kernel via ``kv_len``, padded query rows are sliced off.
    """
    b, hq, s, d = q.shape
    _, hkv, t, _ = k.shape
    sp = (-s) % bq
    tp = (-t) % bt
    if sp:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sp), (0, 0)))
    if tp:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, tp), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, tp), (0, 0)))
    out = flash_attention_raw(q, k, v, causal=causal, bq=bq, bt=bt,
                              kv_len=t, interpret=interpret)
    return out[:, :, :s]
