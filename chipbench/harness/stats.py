"""Percentiles, censoring and the end-to-end metrics of a window.

Percentiles interpolate linearly between order statistics, as the
program's ``core.metrics`` does with ``np.percentile`` (copied so that no
change to the program moves the yardstick).  A request due in the window
and not complete when it closes is censored: its latency is taken as the
time from its due time to the close, a lower bound, and it misses every
limit.  The interactive tenant's tokens are counted to the end of the
wait for the open-loop requests after the close (``Window.drain_end``),
over the time to then; the batch tenant's to the close.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (0..100) by linear interpolation; None when
    there are no values."""
    v = np.sort(np.asarray(values, float))
    if v.size == 0:
        return None
    pos = (v.size - 1) * q / 100.0
    lo = int(np.floor(pos))
    hi = min(lo + 1, v.size - 1)
    return float(v[lo] + (v[hi] - v[lo]) * (pos - lo))


def censored_latencies(due: Sequence[float], done: Sequence[Optional[float]],
                       close: float) -> List[float]:
    """Wall latency of each request from its due time: to its completion,
    or to the window's close where it had none by then."""
    out = []
    for d, c in zip(due, done):
        out.append((c if c is not None and c <= close else close) - d)
    return out


def met(due: Sequence[float], done: Sequence[Optional[float]],
        limits: Sequence[float], close: float) -> List[bool]:
    """Whether each request completed within the window and its limit."""
    return [c is not None and c <= close and c - d <= lim
            for d, c, lim in zip(due, done, limits)]


def size_key(prompt_len: int, output: int) -> str:
    return f"{prompt_len}x{output}"


def isolated_s(cell: Dict, prompt_len: int, output: int) -> float:
    """A request's isolated wall time, as measured alone on the chip when
    the cell was defined (``chipbench/calibrate.py``)."""
    return float(cell["isolated_s"][size_key(prompt_len, output)])


def end_to_end(window, cell: Dict, mix: Dict) -> Dict[str, float]:
    """The cell's end-to-end metrics from one window, plus the counts the
    result line reports."""
    hi_prio = max(int(t["priority"]) for t in mix["tenants"])
    lo_prio = min(int(t["priority"]) for t in mix["tenants"])
    hi = [r for r in window.records.values()
          if r.req.priority == hi_prio and r.sent < window.end]
    due = [r.sent for r in hi]
    done = [r.complete for r in hi]
    lat = censored_latencies(due, done, window.end)
    limits = [cell["sla_scale"] * isolated_s(cell, r.req.prompt_len,
                                             r.req.max_new_tokens)
              for r in hi]
    ok = met(due, done, limits, window.end)

    def tokens(prio, upto):
        return sum(r.tokens.shape[1] for r in window.records.values()
                   if r.req.priority == prio and r.complete is not None
                   and r.complete <= upto)
    return {
        "hi_latency_p50_s": percentile(lat, 50),
        "hi_latency_p90_s": percentile(lat, 90),
        "hi_sla_share": 100.0 * sum(ok) / len(ok) if ok else None,
        "hi_tokens_per_s": (tokens(hi_prio, window.drain_end)
                            / (window.drain_end - window.start)),
        "batch_tokens_per_s": tokens(lo_prio, window.end) / window.seconds,
        "n_hi": len(hi),
        "n_hi_censored": sum(c is None or c > window.end for c in done),
    }
