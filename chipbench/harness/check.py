"""What decides ``correct``: served tokens against the plain reference.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and always holding the longest one and a preempted one where
there is one, is run through the configuration's float32 reference over
its prompt and served tokens.  At each served position the number compared
is the gap by which the served token's reference logit lies below the
reference's best; the widest gap over the sample is held to the cell's
limit.  Greedy decoding serves the best token of the program's own
logits, so a gap only opens where the program's arithmetic strays from the
reference's.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from chipbench.harness import catalog


def sample(records, seed: int, min_tokens: int, max_requests: int) -> List:
    """Finished requests to check: the longest, a preempted one if any,
    then others in a seeded order until ``min_tokens`` served tokens."""
    done = sorted((r for r in records if r.tokens is not None),
                  key=lambda r: r.req.rid)
    if not done:
        return []
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 2])
    longest = max(done, key=lambda r: (r.req.prompt_len + r.tokens.shape[1],
                                       -r.req.rid))
    out = [longest]
    pre = [r for r in done if r.n_preemptions > 0 and r is not longest]
    if pre:
        out.append(pre[int(rng.integers(len(pre)))])
    for i in rng.permutation(len(done)):
        if (sum(r.tokens.shape[1] for r in out) >= min_tokens
                or len(out) >= max_requests):
            break
        if done[i] not in out:
            out.append(done[i])
    return out


def gaps(spec: Dict, params, prompt: np.ndarray, served: np.ndarray,
         quantize: Optional[str] = None) -> np.ndarray:
    """Gap below the reference's best logit at each served position: of the
    served token, or with ``quantize`` of the token that the reference in
    that precision puts first."""
    ref = catalog.reference(spec)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    rows = np.arange(len(prompt) - 1, len(seq))
    want = ref.logits(spec, params, seq, rows)
    pick = served
    if quantize is not None:
        pick = ref.logits(spec, params, seq, rows, quantize=quantize).argmax(-1)
    return want.max(-1) - want[np.arange(len(rows)), pick]


def widest_gap(spec: Dict, params, recs, quantize: Optional[str] = None
               ) -> float:
    return max(float(gaps(spec, params, r.req.prompt[0], r.tokens[0],
                          quantize).max()) for r in recs)
