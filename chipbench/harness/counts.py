"""Operations and bytes of the program's jitted steps, from a configuration
file's sizes.

Every count here is what the algorithm *needs*, a lower bound on what any
implementation moves or computes, so that a share of the roofline built on
it can never pass 100 %:

* FLOPs count each multiply-add of a matrix product as 2, attention only
  over the causal (prefill) or valid (decode) keys, and leave out norms,
  softmax and rotary arithmetic (elementwise, under 1 % here).
* Bytes count each weight read once, each cached key and value that the
  step attends to read once, the step's new keys and values written once,
  and the step's input and output activations.  Nothing is counted for
  the masked capacity of the cache, for copies of it, or for
  re-reading a weight.

The least time of a step on a chip is the larger of FLOPs over the peak
FLOP/s and bytes over the peak bandwidth; :func:`least_time` says which of
the two bounds it.
"""
from __future__ import annotations

from typing import Dict, Tuple

def _item(spec: Dict) -> int:
    """Bytes of one weight or cache element, in the served dtype."""
    return {"bfloat16": 2, "float16": 2, "float32": 4}[spec["serve_dtype"]]


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
    return n * _item(spec)


def head_bytes(spec: Dict) -> int:
    d, *_, v, _ = _dims(spec)
    return v * d * _item(spec)


def weight_bytes(spec: Dict) -> int:
    """Every weight of the model (embedding and head once when tied)."""
    d, *_, layers = _dims(spec)
    n = layers * layer_weight_bytes(spec) + head_bytes(spec)
    if not spec["tie_word_embeddings"]:
        n += head_bytes(spec)
    if spec["model_type"] == "llama":
        n += d * _item(spec)                     # the final RMSNorm scale
    return n


def kv_bytes_per_token(spec: Dict) -> int:
    d, hq, hkv, dh, _, _, layers = _dims(spec)
    return layers * 2 * hkv * dh * _item(spec)


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
    nbytes = (weight_bytes(spec) + row + v * _item(spec)
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
    nbytes = (layer_weight_bytes(spec) + 2 * seq * d * _item(spec)
              + kv_bytes_per_token(spec) // layers * seq)
    return float(flops), float(nbytes)


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


def least_time(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """Least seconds the chip could take, and which bound sets it
    (``"compute"`` or ``"memory"``)."""
    tc = flops / peaks["flops_bf16"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
