"""The program's own wall-clock spans in a reduced window
(``harness/trace.py``), each with the request it belongs to, on the
window's clock.

The program's ``ServingEngine`` and ``PreemptibleExecutor`` mark their
phases with ``jax.profiler.TraceAnnotation`` on the thread that also holds
the harness's markers, so their spans are among ``Reduced.host_spans``:

* ``executor.first_token`` ends when a request's first token is on the
  host, ``executor.decode`` when each later one is;
* ``engine.decide`` covers the arbiter's decision at a step boundary or on
  an idle device, ``engine.preempt`` a preemption from the decision to the
  new request's start or resume;
* ``engine.parked_bytes:<n>`` is a gauge, a zero-length span at every
  change of the bytes that started, not running requests hold on the
  device.

A span belongs to the request last dispatched before it started
(``chipbench.dispatch:<rid>``), the rule ``trace.reduce`` applies to
device runs; it holds with the cells' one batch slot.  Trace time maps to
the window's clock through ``chipbench.open``, whose span encloses the
reading of ``Window.start``.

The names are copied from the program, as ``trace.py`` copies
``_decode``: a span the program renames leaves its metric null instead of
moving it.  A trace with none of them (a program without the spans) gives
None, not a number.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional

from chipbench.harness.trace import DISPATCH, Reduced

FIRST_TOKEN = "executor.first_token"
DECODE = "executor.decode"
DECIDE = "engine.decide"
PREEMPT = "engine.preempt"
PARKED_BYTES = "engine.parked_bytes:"


@dataclasses.dataclass
class Span:
    """One span of the program, on the window's clock."""
    name: str
    start: float
    end: float
    rid: Optional[int]

    @property
    def seconds(self) -> float:
        return self.end - self.start


def program_spans(red: Reduced, window, name: str) -> List[Span]:
    """The spans named ``name``, in start order, each with the request
    last dispatched before it started and on the clock of ``window``
    (``harness/serve.Window``)."""
    dispatches = sorted((s, int(n[len(DISPATCH):])) for n, s, _ in red.host_spans
                        if n.startswith(DISPATCH))
    starts = [s for s, _ in dispatches]
    shift = window.start - red.open
    out = []
    for n, s, e in red.host_spans:
        if n == name:
            k = bisect.bisect_right(starts, s)
            out.append(Span(n, s + shift, e + shift,
                            dispatches[k - 1][1] if k else None))
    return sorted(out, key=lambda sp: sp.start)


def token_times(red: Reduced, window) -> Optional[Dict[int, List[float]]]:
    """For each request, the times at which its tokens reached the host
    (the ends of its ``executor.first_token`` and ``executor.decode``
    spans), in order; None when the trace has no first token at all."""
    first = program_spans(red, window, FIRST_TOKEN)
    if not first:
        return None
    out: Dict[int, List[float]] = {}
    for sp in first + program_spans(red, window, DECODE):
        if sp.rid is not None:
            out.setdefault(sp.rid, []).append(sp.end)
    for times in out.values():
        times.sort()
    return out


def mean_ms(red: Reduced, window, name: str) -> Optional[float]:
    """Mean duration, in ms, of the spans named ``name`` that start in the
    window; None when there are none."""
    t = [sp.seconds for sp in program_spans(red, window, name)
         if window.start <= sp.start < window.end]
    return 1e3 * sum(t) / len(t) if t else None


def parked_peak(red: Reduced) -> Optional[int]:
    """The most bytes the gauge held while the window was open: its
    readings in the window and the level it held when the window opened;
    None when the trace has no reading."""
    readings = sorted((s, int(n[len(PARKED_BYTES):]))
                      for n, s, _ in red.host_spans if n.startswith(PARKED_BYTES))
    before = [b for t, b in readings if t < red.open]
    inside = [b for t, b in readings if red.open <= t < red.close]
    return max(before[-1:] + inside) if before or inside else None
