"""Plain float32 reference of a dense decoder (OLMo, llama family).

Written from the published descriptions, not from the program: pre-norm
blocks of causal self-attention with rotary positions (the ``rotate_half``
form of the published code) and a SwiGLU MLP, grouped-query attention by
giving query head ``h`` the key/value head ``h // (n_heads / n_kv_heads)``,
and a head tied to the embedding or not as the configuration says.  It
reads the benchmark's own weights, one layer at a time in float32 with the
highest matmul precision, so a 9 GB stage fits beside its bfloat16 copy.

``quantize="float8_e4m3fn"`` rounds every weight matrix to fp8 with one
absmax scale per tensor before use: the control that computes the same
model one precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# Attention is computed for this many query rows at a time, so that the
# float32 score matrix of a 56-head stage at 2560 positions fits.
QUERY_BLOCK = 256


def _q(w, quantize: Optional[str]):
    w = w.astype(F32)
    if quantize is None:
        return w
    fmax = float(jnp.finfo(jnp.dtype(quantize)).max)
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-30) / fmax
    return (w / scale).astype(jnp.dtype(quantize)).astype(F32) * scale


def _norm(x, scale, kind: str, eps: float):
    if kind == "rmsnorm":
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps)


def _rope(x, theta: float):
    """x: (S, H, Dh); rotate_half form: [x1 cos - x2 sin, x2 cos + x1 sin]."""
    s, _, dh = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=("norm", "eps", "theta", "quantize"))
def _layer(h, slots, i, *, norm: str, eps: float, theta: float,
           quantize: Optional[str]):
    w = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, False),
                     slots)
    att, mlp = w["mixer"], w["ffn"]
    wq, wk, wv, wo = (_q(att[k], quantize) for k in ("wq", "wk", "wv", "wo"))
    s = h.shape[0]
    hq, hkv, dh = wq.shape[1], wk.shape[1], wq.shape[2]
    x = _norm(h, w["norm1"].get("scale", 1.0), norm, eps)
    q = _rope(jnp.einsum("sd,dhk->shk", x, wq), theta)
    k = _rope(jnp.einsum("sd,dhk->shk", x, wk), theta)
    v = jnp.einsum("sd,dhk->shk", x, wv)
    kv_of = jnp.arange(hq) // (hq // hkv)
    k, v = k[:, kv_of], v[:, kv_of]

    def attend(block):             # QUERY_BLOCK query rows at a time
        qb, rows = block
        scores = jnp.einsum("shk,thk->hst", qb, k) / np.sqrt(dh)
        causal = jnp.arange(s)[None, :] <= rows[:, None]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("hst,thk->shk", jax.nn.softmax(scores, -1), v)
    nb = s // QUERY_BLOCK
    o = jax.lax.map(attend, (q.reshape(nb, QUERY_BLOCK, hq, dh),
                             jnp.arange(s).reshape(nb, QUERY_BLOCK))
                    ).reshape(s, hq, dh)
    h = h + jnp.einsum("shk,hkd->sd", o, wo)
    x = _norm(h, w["norm2"].get("scale", 1.0), norm, eps)
    up = x @ _q(mlp["w_in"], quantize)
    gate = x @ _q(mlp["w_gate"], quantize)
    return h + (jax.nn.silu(gate) * up) @ _q(mlp["w_out"], quantize)


@functools.partial(jax.jit, static_argnames=("norm", "eps", "tied", "quantize"))
def _head(h, params, rows, *, norm: str, eps: float, tied: bool,
          quantize: Optional[str]):
    x = _norm(h[rows], params["final_norm"].get("scale", 1.0), norm, eps)
    if tied:
        return x @ _q(params["embed"]["table"], quantize).T
    return x @ _q(params["lm_head"]["w"], quantize)


@functools.partial(jax.jit, static_argnames=("quantize",))
def _embed(table, tokens, *, quantize: Optional[str]):
    return _q(table, quantize)[tokens]


def _norm_kind(spec: Dict):
    if spec["model_type"] == "olmo":
        return "layernorm_np", float(spec["norm_eps"])
    return "rmsnorm", float(spec["rms_norm_eps"])


def logits(spec: Dict, params, tokens: np.ndarray, rows: np.ndarray,
           quantize: Optional[str] = None, pad_to: int = 512) -> np.ndarray:
    """float32 logits at positions ``rows`` of the token sequence ``tokens``
    (1-D).  The sequence is padded at its end to a multiple of ``pad_to``,
    and the rows to a multiple of 128, so that a few shapes cover every
    request; under the causal mask the padding changes no earlier
    position."""
    norm, eps = _norm_kind(spec)
    n = len(tokens)
    padded = np.zeros(-(-n // pad_to) * pad_to, np.int32)
    padded[:n] = tokens
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"]["table"], jnp.asarray(padded),
                   quantize=quantize)
        for i in range(int(spec["num_hidden_layers"])):
            h = _layer(h, params["slots"]["slot0"], jnp.int32(i), norm=norm,
                       eps=eps, theta=float(spec["rope_theta"]),
                       quantize=quantize)
        picked = np.zeros(-(-len(rows) // 128) * 128, np.int32)
        picked[:len(rows)] = rows
        out = _head(h, params, jnp.asarray(picked), norm=norm, eps=eps,
                    tied=bool(spec["tie_word_embeddings"]), quantize=quantize)
    return np.asarray(out, np.float32)[:len(rows)]
