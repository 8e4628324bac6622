"""Operations and bytes of the program's jitted steps, from a configuration
file's sizes.

The counts of each step are its family's (``chipbench/families/<name>.py``,
found by the configuration's ``reference`` key); the functions here forward
to them.  Every count is what the algorithm *needs*, a lower bound on what
any implementation moves or computes, so that a share of the roofline built
on it can never pass 100 %.

The least time of a step on a chip is the larger of FLOPs over the peak
FLOP/s and bytes over the peak bandwidth; :func:`least_time` says which of
the two bounds it.
"""
from __future__ import annotations

from typing import Dict, Tuple

from chipbench.harness import catalog


def item_bytes(spec: Dict) -> int:
    """Bytes of one weight or cache element, in the served dtype."""
    return {"bfloat16": 2, "float16": 2, "float32": 4}[spec["serve_dtype"]]


def weight_bytes(spec: Dict) -> int:
    """Every weight of the model."""
    return catalog.family(spec).weight_bytes(spec)


def kv_bytes_per_token(spec: Dict) -> int:
    """Bytes of cached keys and values a token adds."""
    return catalog.family(spec).kv_bytes_per_token(spec)


def decode_step(spec: Dict, pos: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of one ``_decode`` call, batch 1, with ``pos``
    tokens already in the cache."""
    return catalog.family(spec).decode_step(spec, pos)


def prefill_period(spec: Dict, seq: int) -> Tuple[float, float]:
    """``(flops, bytes)`` of one ``_period_prefill`` call over a prompt of
    ``seq`` tokens."""
    return catalog.family(spec).prefill_period(spec, seq)


def request_flops(spec: Dict, prompt_len: int, n_tokens: int) -> float:
    """Forward FLOPs a request of ``prompt_len`` tokens that was served
    ``n_tokens`` needs, whichever steps computed them."""
    return catalog.family(spec).request_flops(spec, prompt_len, n_tokens)


def least_time(flops: float, nbytes: float, peaks: Dict) -> Tuple[float, str]:
    """Least seconds the chip could take, and which bound sets it
    (``"compute"`` or ``"memory"``)."""
    tc = flops / peaks["flops_bf16"]
    tm = nbytes / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
