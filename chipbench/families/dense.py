"""The dense decoder (OLMo, llama family): pre-norm blocks of causal
grouped-query attention and a SwiGLU MLP, one ``("attn", "mlp")`` layer a
period.

A configuration of this family names it by ``"reference": "dense"``.  This
file states it as the program's ``ArchConfig``, lays out its weights and
counts its steps; ``chipbench/reference/dense.py`` is its plain reference.

The counts are what the algorithm *needs*, a lower bound on what any
implementation moves or computes, so that a share of the roofline built on
them can never pass 100 %:

* FLOPs count each multiply-add of a matrix product as 2, attention only
  over the causal (prefill) or valid (decode) keys, and leave out norms,
  softmax and rotary arithmetic (elementwise, under 1 % here).
* Bytes count each weight read once, each cached key and value that the
  step attends to read once, the step's new keys and values written once,
  and the step's input and output activations.  Nothing is counted for
  the masked capacity of the cache, for copies of it, or for
  re-reading a weight.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

from chipbench.harness.counts import item_bytes

# model_type of the published config -> the norm it uses.  OLMo-1B uses a
# LayerNorm with no scale or bias (arXiv:2402.00838, section 2.1); the
# llama family an RMSNorm with a scale.
NORMS = {"olmo": "layernorm_np", "llama": "rmsnorm"}

# Small sizes of the family for the CPU tests: the tiny cell, the
# reference against the program, and (at one layer) the counts against
# XLA's, where the elementwise work the counts leave out stays under 5 %.
TINY = dict(num_hidden_layers=2, hidden_size=256, intermediate_size=512,
            num_attention_heads=8, num_key_value_heads=2, vocab_size=512)
# The tiny cell's limit on max_logit_gap (conftest.tiny_root: 200 tokens,
# 12 requests), lower**(1/3) * upper**(2/3) as calibrate.py sets a cell's:
# over olmo-1b and deepseek-coder-33b-pp8 at TINY, 12 seeds, windows of 1,
# 1.5 and 3 s (72 readings, CPU), the program's largest gap 0.0497 and the
# fp8 control's smallest 0.2006.
TINY_LIMIT = 0.126


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


def weight_shapes(spec: Dict) -> Dict:
    """Leaf name -> (shape, initialisation) in the program's parameter
    layout, layers stacked on the leading axis: uniform by fan-in, norm
    scales ones."""
    L = int(spec["num_hidden_layers"])
    d = int(spec["hidden_size"])
    hq = int(spec["num_attention_heads"])
    hkv = int(spec["num_key_value_heads"])
    dh = d // hq
    f = int(spec["intermediate_size"])
    v = int(spec["vocab_size"])
    out = {
        "embed/table": ((v, d), ("uniform", d)),
        "slots/slot0/mixer/wq": ((L, d, hq, dh), ("uniform", d)),
        "slots/slot0/mixer/wk": ((L, d, hkv, dh), ("uniform", d)),
        "slots/slot0/mixer/wv": ((L, d, hkv, dh), ("uniform", d)),
        "slots/slot0/mixer/wo": ((L, hq, dh, d), ("uniform", hq * dh)),
        "slots/slot0/ffn/w_in": ((L, d, f), ("uniform", d)),
        "slots/slot0/ffn/w_gate": ((L, d, f), ("uniform", d)),
        "slots/slot0/ffn/w_out": ((L, f, d), ("uniform", f)),
    }
    if NORMS[spec["model_type"]] == "rmsnorm":
        out["slots/slot0/norm1/scale"] = ((L, d), "ones")
        out["slots/slot0/norm2/scale"] = ((L, d), "ones")
        out["final_norm/scale"] = ((d,), "ones")
    if not spec["tie_word_embeddings"]:
        out["lm_head/w"] = ((d, v), ("uniform", d))
    return out


def empty_nodes(spec: Dict) -> List[str]:
    """The norm nodes of the layout, which a non-parametric LayerNorm
    keeps with no leaves."""
    return ["slots/slot0/norm1", "slots/slot0/norm2", "final_norm"]


def _dims(spec: Dict):
    d = int(spec["hidden_size"])
    hq = int(spec["num_attention_heads"])
    hkv = int(spec["num_key_value_heads"])
    return (d, hq, hkv, d // hq, int(spec["intermediate_size"]),
            int(spec["vocab_size"]), int(spec["num_hidden_layers"]))


def layer_weight_bytes(spec: Dict) -> int:
    d, hq, hkv, dh, f, _, _ = _dims(spec)
    n = d * (hq + 2 * hkv) * dh + hq * dh * d + 3 * d * f
    if spec["model_type"] == "llama":
        n += 2 * d                               # the two RMSNorm scales
    return n * item_bytes(spec)


def head_bytes(spec: Dict) -> int:
    d, *_, v, _ = _dims(spec)
    return v * d * item_bytes(spec)


def weight_bytes(spec: Dict) -> int:
    """Every weight of the model (embedding and head once when tied)."""
    d, *_, layers = _dims(spec)
    n = layers * layer_weight_bytes(spec) + head_bytes(spec)
    if not spec["tie_word_embeddings"]:
        n += head_bytes(spec)
    if spec["model_type"] == "llama":
        n += d * item_bytes(spec)                # the final RMSNorm scale
    return n


def kv_bytes_per_token(spec: Dict) -> int:
    d, hq, hkv, dh, _, _, layers = _dims(spec)
    return layers * 2 * hkv * dh * item_bytes(spec)


def decode_step(spec: Dict, pos: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of one ``_decode`` call, batch 1, with ``pos``
    tokens already in the cache: every layer and the head, attention over
    the ``pos + 1`` valid keys.  Bytes: every weight but the rows of an
    untied embedding table that the token does not use, the ``pos`` cached
    keys and values read, the new ones written, the logits written."""
    d, hq, hkv, dh, f, v, layers = _dims(spec)
    per_layer = (2 * d * (hq + 2 * hkv) * dh + 2 * hq * dh * d
                 + 2 * 2 * hq * dh * (pos + 1) + 2 * 3 * d * f)
    flops = layers * per_layer + 2 * d * v
    row = head_bytes(spec) // v                  # one embedding row
    nbytes = (weight_bytes(spec) + row + v * item_bytes(spec)
              + kv_bytes_per_token(spec) * (pos + 1))
    if not spec["tie_word_embeddings"]:
        nbytes -= head_bytes(spec)               # the table: one row only
    return float(flops), float(nbytes)


def prefill_period(spec: Dict, seq: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of one ``_period_prefill`` call: one layer over a
    prompt of ``seq`` tokens, attention over the causal half of the score
    matrix.  Bytes: the layer's weights, the activations in and out, the
    keys and values written."""
    d, hq, hkv, dh, f, _, layers = _dims(spec)
    flops = (2 * seq * d * (hq + 2 * hkv) * dh + 2 * seq * hq * dh * d
             + 2 * 2 * hq * dh * seq * (seq + 1) // 2 + 2 * 3 * seq * d * f)
    nbytes = (layer_weight_bytes(spec) + 2 * seq * d * item_bytes(spec)
              + kv_bytes_per_token(spec) // layers * seq)
    return float(flops), float(nbytes)


def prefill_beyond_need(spec: Dict, seq: int) -> float:
    """FLOPs a ``_period_prefill`` call computes beyond
    :func:`prefill_period`'s count: the masked half of the score matrix,
    which the program computes and the count leaves out."""
    d, hq, _, dh, *_ = _dims(spec)
    return float(2 * 2 * hq * dh * seq * (seq - 1) // 2)


def request_flops(spec: Dict, prompt_len: int, n_tokens: int) -> float:
    """Forward FLOPs a request of ``prompt_len`` tokens that was served
    ``n_tokens`` needs, whichever steps computed them: the prefill of
    every layer, the head at the last prompt position, then a decode step
    for each token after the first."""
    d, *_, v, layers = _dims(spec)
    flops = layers * prefill_period(spec, prompt_len)[0] + 2 * d * v
    for j in range(n_tokens - 1):
        flops += decode_step(spec, prompt_len + j)[0]
    return flops
