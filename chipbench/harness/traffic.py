"""The one traffic generator: a mix file in, requests out.

A mix (``chipbench/traffic/<mix>.json``) lists tenants.  An ``open``
tenant sends on a schedule whatever the server does; a ``closed`` tenant
keeps ``outstanding`` requests in the server and sends the next one when
one completes.  The cell's file (``chipbench/cells/<cell>.json``) gives
the open tenants' rates, as fixed numbers.

Lengths come from a published distribution, stated in the mix as a
lognormal (median and spread) for the prompt and for the output of each
tenant.  A tenant's ``size_cycle`` requests take those distributions'
evenly spaced quantiles, prompt and output paired and put in order by the
mix's own ``shape_seed``; a prompt that would not leave room for its output
within ``max_context`` is cut to fit, as a client cuts what it sends.  The
arrival schedule is drawn from ``shape_seed`` too (an ON/OFF source:
bursts, then idle gaps).  So every seed sends the same lengths at the same
moments, and the seed draws only the token ids: runs on different seeds
do the same work and differ in what the requests say.
"""
from __future__ import annotations

import dataclasses
import statistics
from typing import Dict, List, Sequence, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class OnOff:
    """ON/OFF source: Poisson at ``rate_on`` while ON, silent while OFF,
    exponential dwell times with means ``mean_on`` and ``mean_off``.  The
    ``bursty`` parametrisation is that of the program's
    ``workloads.arrivals.MMPP.bursty`` (an MMPP with ``rate_off = 0``),
    copied here so that no change to the program moves the traffic."""
    rate_on: float
    mean_on: float
    mean_off: float

    @classmethod
    def bursty(cls, rate: float, duty: float, per_burst: float) -> "OnOff":
        """ON for ``duty`` of each cycle at ``rate / duty``; a cycle lasts
        ``per_burst / rate`` on average, so a burst brings ``per_burst``
        arrivals on average and the long-run rate is ``rate``."""
        cycle = per_burst / rate
        return cls(rate_on=rate / duty, mean_on=duty * cycle,
                   mean_off=(1.0 - duty) * cycle)

    def cycles(self, rng: np.random.Generator, horizon: float
               ) -> List[Tuple[float, np.ndarray]]:
        """ON/OFF cycles covering ``[0, horizon)``, each as (length,
        arrival offsets inside it); the last one is cut at the horizon."""
        out, t = [], 0.0
        while t < horizon:
            on = rng.exponential(self.mean_on)
            offs, dt = [], rng.exponential(1.0 / self.rate_on)
            while dt < on:
                offs.append(dt)
                dt += rng.exponential(1.0 / self.rate_on)
            off = rng.exponential(self.mean_off)
            length = min(on + off, horizon - t)
            out.append((length, np.asarray([o for o in offs if o < length])))
            t += on + off
        return out


@dataclasses.dataclass
class Request:
    """One request as the client sends it.  ``due`` is seconds after the
    window opens (open tenants) or None (closed tenants send on
    completion)."""
    rid: int
    tenant: str
    priority: int
    prompt: np.ndarray            # (1, prompt_len) int32
    max_new_tokens: int
    due: float | None = None

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[1])


def quantiles(dist: Dict, k: int) -> np.ndarray:
    """The lognormal ``dist``'s quantiles at (i + 1/2)/k, i < k, rounded to
    whole tokens and held at or above ``dist["min"]``."""
    z = np.asarray([statistics.NormalDist().inv_cdf((i + 0.5) / k)
                    for i in range(k)])
    v = np.rint(float(dist["median"]) * np.exp(float(dist["sigma"]) * z))
    return np.maximum(v, int(dist["min"])).astype(int)


def size_cycle(tenant: Dict, max_context: int, rng: np.random.Generator
               ) -> List[Tuple[int, int]]:
    """The tenant's ``size_cycle`` (prompt_len, output) pairs in the order
    it sends them: quantiles of each length distribution, outputs paired
    with prompts and the pairs ordered by ``rng``; a prompt is cut so that
    prompt and output fit ``max_context``."""
    k = int(tenant["size_cycle"])
    lengths = tenant["lengths"]
    prompts = quantiles(lengths["prompt"], k)
    outputs = np.minimum(quantiles(lengths["output"], k),
                         max_context - int(lengths["prompt"]["min"]))
    outputs = outputs[rng.permutation(k)]
    pairs = [(int(min(p, max_context - o)), int(o))
             for p, o in zip(prompts, outputs)]
    return [pairs[i] for i in rng.permutation(k)]


def prompt(rng: np.random.Generator, plen: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, (1, plen), dtype=np.int64).astype(np.int32)


def open_schedule(tenant: Dict, rate: float, seconds: float,
                  shape_seed: int) -> np.ndarray:
    """Arrival offsets in ``[0, seconds)`` of the mix's ON/OFF source at
    ``rate``, drawn from the mix's own ``shape_seed``: the same on every
    seed of a cell."""
    arr = tenant["arrivals"]
    if arr["process"] != "mmpp_on_off":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    src = OnOff.bursty(rate, arr["duty"], arr["per_burst"])
    out, t = [], 0.0
    for length, offs in src.cycles(np.random.default_rng(shape_seed), seconds):
        out.extend(t + offs)
        t += length
    return np.asarray(out, float)


class Traffic:
    """All requests of one run: the open tenants' schedule, and for each
    closed tenant an endless stream; every tenant sends its size cycle
    over and over."""

    def __init__(self, mix: Dict, cell: Dict, vocab: int, seed: int,
                 seconds: float):
        self.mix, self.vocab = mix, vocab
        self._rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 1])
        self._next_rid = 0
        self.open: List[Request] = []
        self._closed: Dict[str, Tuple[Dict, List[Tuple[int, int]]]] = {}
        self.cycles: Dict[str, List[Tuple[int, int]]] = {}
        shape_seed = int(mix["shape_seed"])
        for i, t in enumerate(mix["tenants"]):
            cycle = size_cycle(t, int(mix["max_context"]),
                               np.random.default_rng([shape_seed, i]))
            self.cycles[t["name"]] = cycle
            if t["loop"] == "open":
                rate = float(cell["rates_per_s"][t["name"]])
                due = open_schedule(t, rate, seconds, shape_seed)
                for j, at in enumerate(due):
                    plen, out = cycle[j % len(cycle)]
                    self.open.append(self._make(t, plen, out, float(at)))
            elif t["loop"] == "closed":
                self._closed[t["name"]] = (t, cycle)
            else:
                raise ValueError(f"tenant {t['name']}: loop {t['loop']!r}")
        self._sent: Dict[str, int] = {name: 0 for name in self._closed}

    def _make(self, t: Dict, plen: int, out: int, due) -> Request:
        r = Request(rid=self._next_rid, tenant=t["name"],
                    priority=int(t["priority"]),
                    prompt=prompt(self._rng, plen, self.vocab),
                    max_new_tokens=int(out), due=due)
        self._next_rid += 1
        return r

    @property
    def closed_tenants(self) -> Sequence[Dict]:
        return [t for t, _ in self._closed.values()]

    def next_closed(self, name: str) -> Request:
        t, cycle = self._closed[name]
        plen, out = cycle[self._sent[name] % len(cycle)]
        self._sent[name] += 1
        return self._make(t, plen, out, None)

    def shapes(self) -> List[Tuple[int, int]]:
        """Every prompt length the run can send, with the longest output
        sent after it: running each once covers every program the window
        runs."""
        out: Dict[int, int] = {}
        for cycle in self.cycles.values():
            for plen, n in cycle:
                out[plen] = max(out.get(plen, 0), n)
        return sorted(out.items())
