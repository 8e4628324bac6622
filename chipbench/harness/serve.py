"""One measured window of the program's ``ServingEngine``, timed on the
host's wall clock from the client's side.

The engine keeps a virtual clock of its own (Algorithm-1 predictions), so
none of its timestamps is read here.  Instead each event of its bus is
stamped with ``time.perf_counter()`` in a subscriber; the bus calls its
subscribers synchronously, so a stamp is the moment the engine reached
that point.  At ``complete`` every token of the request is already on the
host.

* An open-loop request is due at the window's start plus its offset.  If
  the engine takes it in (``submit``) before it is due, the subscriber
  holds the host until it is due: the engine cannot serve a request before
  the client has sent it.  If the engine takes it in late, the wait counts.
* A closed-loop tenant keeps its requests outstanding: its ``complete``
  subscriber sends the next one, until the window closes.
* The window closes after ``seconds``: from then on the client sends
  nothing.  It still waits for every open-loop request it sent, so that
  the open tenants' rate is all of their work over all of its time (a
  request that would end just past the close moves that rate by a share
  of the time, not by its whole length), for at most ``DRAIN_S``.  The
  first event past that wait ends ``run()`` (an exception raised from a
  subscriber).  Latencies and every other count are censored at the
  close, as if the wait had not been.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from chipbench.harness.trace import DISPATCH, HOLD, OPEN
from chipbench.harness.traffic import Request, Traffic

DRAIN_S = 60.0    # the longest wait past the close for open-loop requests


class WindowClosed(Exception):
    """Raised from a bus subscriber at the first event past the window
    and the wait for its open-loop requests."""


@dataclasses.dataclass
class Record:
    """What the client saw of one request, on the host's wall clock."""
    req: Request
    sent: float                      # when the client sent it (due time)
    submit: Optional[float] = None   # the engine took it in
    complete: Optional[float] = None
    dispatches: int = 0
    preempts: int = 0
    tokens: Optional[np.ndarray] = None      # (1, n) served tokens
    n_preemptions: int = 0


@dataclasses.dataclass
class Window:
    start: float
    end: float
    records: Dict[int, Record]
    engine_returned: bool            # run() ended before the window did
    drained: Optional[float] = None  # the wait's end: the last open-loop
    #                                  request's completion, or the close

    @property
    def drain_end(self) -> float:
        return self.end if self.drained is None else self.drained

    @property
    def seconds(self) -> float:
        return self.end - self.start


def _engine_request(r: Request, arch: str, arrival: float):
    from repro.serving import InferenceRequest
    return InferenceRequest(rid=r.rid, arch=arch, prompt=r.prompt,
                            max_new_tokens=r.max_new_tokens,
                            priority=r.priority, arrival=arrival,
                            tenant=r.tenant)


def run_window(engine, traffic: Traffic, arch: str, seconds: float,
               clock=time.perf_counter, annotate: bool = False,
               drain_s: float = DRAIN_S) -> Window:
    """Serve ``traffic`` through ``engine.run`` for ``seconds`` of wall
    time, and then until every open-loop request has completed, for at
    most ``drain_s``; the engine is used only through ``run``, ``submit``,
    ``events`` and the results it appends to ``completed``.  With
    ``annotate`` the window's opening, each dispatch and each hold are
    marked in the profiler's trace (see ``harness/trace.py``)."""
    if annotate:
        from jax.profiler import TraceAnnotation as mark
    else:
        mark = contextlib.nullcontext
    bus = engine.events
    records: Dict[int, Record] = {}
    with mark(OPEN):
        start = clock()
    end = start + seconds
    initial = []
    for r in traffic.open:
        records[r.rid] = Record(r, sent=start + r.due)
        initial.append(_engine_request(r, arch, r.due))
    for t in traffic.closed_tenants:
        for _ in range(int(t["outstanding"])):
            r = traffic.next_closed(t["name"])
            records[r.rid] = Record(r, sent=start)
            initial.append(_engine_request(r, arch, 0.0))

    waiting = {r.rid for r in traffic.open}
    stopped = [end]

    def close_if_past(now: float) -> None:
        if now >= end and (not waiting or now >= end + drain_s):
            stopped[0] = now
            raise WindowClosed

    def on_submit(ev) -> None:
        rec = records[ev.tid]
        now = clock()
        if now < rec.sent:
            with mark(HOLD):
                time.sleep(rec.sent - now)
            now = clock()
        rec.submit = now
        close_if_past(now)

    def on_dispatch(ev) -> None:
        with mark(f"{DISPATCH}{ev.tid}"):
            records[ev.tid].dispatches += 1
        close_if_past(clock())

    def on_preempt(ev) -> None:
        records[ev.tid].preempts += 1
        close_if_past(clock())

    def on_complete(ev) -> None:
        now = clock()
        rec = records[ev.tid]
        rec.complete = now
        result = engine.completed[-1]
        assert result.rid == ev.tid, (result.rid, ev.tid)
        rec.tokens = np.asarray(result.tokens)
        rec.n_preemptions = int(result.n_preemptions)
        waiting.discard(ev.tid)
        if rec.req.due is None and now < end:
            nxt = traffic.next_closed(rec.req.tenant)
            records[nxt.rid] = Record(nxt, sent=now)
            engine.submit(_engine_request(nxt, arch, ev.t), ev.t)
        close_if_past(now)

    handlers = {"submit": on_submit, "dispatch": on_dispatch,
                "preempt": on_preempt, "complete": on_complete}
    detach = bus.subscribe_map(handlers)
    returned = False
    try:
        engine.run(initial)
        returned = True
    except WindowClosed:
        pass
    finally:
        detach()
    if waiting:
        drained = stopped[0]
    else:
        drained = max([end] + [records[r.rid].complete for r in traffic.open])
    return Window(start=start, end=end, records=records,
                  engine_returned=returned, drained=drained)


def serve(cell, model, params, traffic: Traffic, seconds: float,
          annotate: bool = False) -> Window:
    """One window of ``cell``'s traffic through a fresh ``ServingEngine``
    as the benchmark configures it: PREMA, Algorithm-3 mechanism choice,
    the defaults otherwise (one batch slot).  The engine, and with it its
    device state, is dropped when this returns."""
    from repro.serving import EngineConfig, ServingEngine
    engine = ServingEngine({cell.config_name: (model, params)},
                           cfg=EngineConfig(policy="prema", mechanism="dynamic"))
    return run_window(engine, traffic, cell.config_name, seconds,
                      annotate=annotate)


def due_in_window(w: Window, priority: int) -> List[Record]:
    return [r for r in w.records.values()
            if r.req.priority == priority and r.sent < w.end]
