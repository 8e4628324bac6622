"""The window's close and the wait after it, driven by a scripted engine
on a fake clock: the client sends nothing past the close, waits for its
open-loop requests, and no longer than the wait's limit."""
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench.harness import serve
from chipbench.harness.traffic import Request


class _Clock:
    t = 0.0

    def __call__(self):
        return self.t


class _Engine:
    """Plays ``script``, (time, event, rid) in order, through the bus the
    window subscribes to; a ``complete`` hands over the request's tokens."""

    def __init__(self, script, clock):
        self.script, self.clock = script, clock
        self.completed, self.submitted, self.handlers = [], [], {}
        self.events = self

    def subscribe_map(self, handlers):
        self.handlers = handlers
        return lambda: None

    def submit(self, req, at):
        self.submitted.append(req.rid)

    def run(self, initial):
        for t, kind, rid in self.script:
            self.clock.t = t
            if kind == "complete":
                self.completed.append(SimpleNamespace(
                    rid=rid, tokens=np.zeros((1, 3), np.int32),
                    n_preemptions=0))
            self.handlers[kind](SimpleNamespace(tid=rid, t=t))


class _Traffic:
    """Two open-loop requests due at 1 s and 8 s, and a closed tenant with
    one request outstanding."""

    def __init__(self):
        self.open = [self._req(0, 9, 1.0), self._req(1, 9, 8.0)]
        self.closed_tenants = [{"name": "batch", "outstanding": 1}]
        self._rid = 100

    @staticmethod
    def _req(rid, prio, due):
        return Request(rid, "t", prio, np.zeros((1, 4), np.int32), 3, due=due)

    def next_closed(self, name):
        self._rid += 1
        return self._req(self._rid, 1, None)


def _window(script, drain_s=serve.DRAIN_S):
    clock = _Clock()
    engine = _Engine(script, clock)
    w = serve.run_window(engine, _Traffic(), "m", 10.0, clock=clock,
                         drain_s=drain_s)
    return w, engine


def test_the_client_waits_for_its_open_requests_past_the_close():
    w, engine = _window([(1.0, "submit", 0), (2.0, "complete", 0),
                         (8.0, "submit", 1), (9.0, "complete", 101),
                         (11.0, "complete", 102),     # batch, past the close
                         (12.5, "complete", 1),       # the wait ends here
                         (13.0, "complete", 103)])    # never reached
    assert w.drained == 12.5 and w.drain_end == 12.5
    assert w.records[1].complete == 12.5 and 103 not in w.records
    assert engine.submitted == [102]                  # nothing after 10 s


def test_the_wait_has_a_limit():
    w, _ = _window([(1.0, "submit", 0), (2.0, "complete", 0),
                    (8.0, "submit", 1), (11.0, "dispatch", 101),
                    (14.0, "dispatch", 101), (30.0, "complete", 1)],
                   drain_s=3.0)
    assert w.drained == 14.0 and w.records[1].complete is None


def test_open_requests_done_before_the_close_end_at_the_close():
    w, _ = _window([(1.0, "submit", 0), (2.0, "complete", 0),
                    (8.0, "submit", 1), (9.0, "complete", 1),
                    (10.5, "complete", 101), (11.0, "complete", 102)])
    assert w.drained == 10.0 and w.drain_end == w.end
    assert 102 not in w.records
