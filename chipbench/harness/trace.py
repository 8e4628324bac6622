"""Reduce a profiler trace (``.xplane.pb``) of one window to what the
per-layer metrics read.

The device's work is read from the ``XLA Modules`` line of each
``/device:TPU:<n>`` plane: one event per run of a compiled program, named
after its jitted function (``jit__decode``, ``jit__period_prefill``...).
The harness writes markers into the host's part of the same trace with
``jax.profiler.TraceAnnotation``, so that both sides share one clock:

* ``chipbench.open`` where the window opens;
* ``chipbench.dispatch:<rid>`` when the engine starts or resumes a request;
* ``chipbench.hold`` spans while the client holds a request not yet due.

A program run belongs to the request last dispatched before it started:
the engine enqueues a request's steps only after its dispatch, and the next
dispatch comes only after the checkpoint that waits for them.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

OPEN = "chipbench.open"
DISPATCH = "chipbench.dispatch:"
HOLD = "chipbench.hold"


def program_name(event_name: str) -> str:
    """``jit__decode(123)`` -> ``_decode``: the jitted function's name."""
    name = re.sub(r"\(.*\)$", "", event_name).strip()
    return name[4:] if name.startswith("jit_") else name


@dataclasses.dataclass
class Run:
    """One run of a compiled program on a device."""
    program: str
    start: float          # seconds on the trace's clock
    end: float
    rid: Optional[int] = None

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Reduced:
    open: float                          # window, on the trace's clock
    close: float
    runs: List[Run]                      # device 0, in start order
    busy_s: float                        # mean over devices in the window
    n_devices: int
    host_spans: List[Tuple[str, float, float]]  # main host thread

    @property
    def window_s(self) -> float:
        return self.close - self.open


def _union(intervals: List[Tuple[float, float]]) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clip(s: float, e: float, lo: float, hi: float):
    return max(s, lo), min(e, hi)


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)


def reduce(data, seconds: float) -> Reduced:
    """Reduce a ``jax.profiler.ProfileData``; the window is ``seconds``
    long from the ``chipbench.open`` marker."""
    host_spans: List[Tuple[str, float, float]] = []
    devices = []
    open_t = None
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    devices.append([Run(program_name(e.name), e.start_ns * 1e-9,
                                        (e.start_ns + e.duration_ns) * 1e-9)
                                    for e in line.events])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans = [(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events]
                if any(n.startswith(OPEN) for n, _, _ in spans):
                    host_spans = spans
                    open_t = min(s for n, s, _ in spans if n.startswith(OPEN))
    if open_t is None:
        raise ValueError(f"no {OPEN} marker on the host")
    if not devices:
        raise ValueError("no XLA Modules line on any TPU plane")
    close_t = open_t + seconds
    busy = [_union([_clip(r.start, r.end, open_t, close_t) for r in runs
                    if r.end > open_t and r.start < close_t])
            for runs in devices]
    runs = sorted((r for r in devices[0] if r.end > open_t and r.start < close_t),
                  key=lambda r: r.start)
    dispatches = sorted((s, int(n[len(DISPATCH):])) for n, s, _ in host_spans
                        if n.startswith(DISPATCH))
    k, rid = 0, None
    for r in runs:
        while k < len(dispatches) and dispatches[k][0] <= r.start:
            rid = dispatches[k][1]
            k += 1
        r.rid = rid
    return Reduced(open=open_t, close=close_t, runs=runs,
                   busy_s=sum(busy) / len(busy), n_devices=len(devices),
                   host_spans=host_spans)


def idle_gaps(red: Reduced) -> List[Tuple[float, float]]:
    """Device-idle intervals of device 0 inside the window."""
    out, t = [], red.open
    for r in red.runs:
        if r.start > t:
            out.append((t, r.start))
        t = max(t, r.end)
    if red.close > t:
        out.append((t, red.close))
    return out


def gaps_by_host(red: Reduced, top: int = 10) -> List[List]:
    """Idle device time by what the host was doing meanwhile: each gap is
    split over the host spans that overlap it, innermost span first (the
    shortest one covering a point names it), and what no span covers is
    ``host: untraced``."""
    gaps = idle_gaps(red)
    ends = [e for _, e in gaps]
    inside: List[List[Tuple[float, str, float, float]]] = [[] for _ in gaps]
    for n, s, e in red.host_spans:
        if n.startswith(OPEN) or n.startswith(DISPATCH):
            continue
        i = bisect.bisect_right(ends, s)
        while i < len(gaps) and gaps[i][0] < e:
            inside[i].append((e - s, n, s, e))
            i += 1
    totals: Dict[str, float] = {}
    for (gs, ge), spans in zip(gaps, inside):
        pieces = [(gs, ge)]
        for _, name, s, e in sorted(spans):
            rest = []
            for ps, pe in pieces:
                a, b = max(ps, s), min(pe, e)
                if a < b:
                    totals[name] = totals.get(name, 0.0) + (b - a)
                    rest += [p for p in ((ps, a), (b, pe)) if p[0] < p[1]]
                else:
                    rest.append((ps, pe))
            pieces = rest
        left = sum(pe - ps for ps, pe in pieces)
        if left > 0:
            totals["host: untraced"] = totals.get("host: untraced", 0.0) + left
    return [[n, s] for n, s in sorted(totals.items(), key=lambda x: -x[1])[:top]]


def device_time_by_program(red: Reduced, top: int = 10) -> List[List]:
    totals: Dict[str, float] = {}
    for r in red.runs:
        a, b = _clip(r.start, r.end, red.open, red.close)
        totals[r.program] = totals.get(r.program, 0.0) + (b - a)
    return [[n, s] for n, s in sorted(totals.items(), key=lambda x: -x[1])[:top]]


def step_positions(red: Reduced, prompt_len: Dict[int, int]
                   ) -> List[Tuple[Run, int]]:
    """Each ``_decode`` and ``_period_prefill`` run with its size: the
    prompt length for a prefill period, the number of tokens already in
    the cache for a decode step.  A request's decode position restarts at
    its prompt length after any prefill period (a killed request prefills
    again); runs of a request first dispatched before the trace began are
    left out, as their position is unknown."""
    nxt: Dict[int, int] = {}
    out = []
    for r in red.runs:
        if r.rid is None or r.rid not in prompt_len:
            continue
        if r.program == "_period_prefill":
            nxt[r.rid] = prompt_len[r.rid]
            out.append((r, prompt_len[r.rid]))
        elif r.program == "_decode" and r.rid in nxt:
            out.append((r, nxt[r.rid]))
            nxt[r.rid] += 1
    return out
