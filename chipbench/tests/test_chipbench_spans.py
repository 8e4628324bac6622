"""The program's spans in a reduced window (``harness/spans.py``) and the
five metrics read from them, on a window written out span by span."""
import numpy as np
import pytest

from chipbench import run
from chipbench.harness import spans
from chipbench.harness.catalog import reader
from chipbench.harness.serve import Record, Window
from chipbench.harness.trace import Reduced
from chipbench.harness.traffic import Request
from chipbench.tests.conftest import ROOT

# The trace's window opens at 100 s and closes at 110 s; the host's wall
# clock read 1000 s at the open, so the window runs 1000-1010 there.
#   rid 1 (interactive, due 1000.5): first token at 1001.0, tokens at
#     1001.2 and 1001.4, preempted for rid 2 (batch, one token at 1001.7),
#     resumed, a token at 1002.3: gaps 0.2, 0.2 and 0.9 s
#   rid 3 (interactive, due 1008.0): no first token, censored: 2.0 s
#   rid 4 (interactive, due 1001.0): first token at 1003.5: 2.5 s
#   rid 5 (interactive, due 1009.5): first token past the close: 0.5 s
HOST = [
    ("engine.parked_bytes:0", 99.0, 99.0),
    ("engine.decide", 99.5, 99.6),                # before the open
    ("chipbench.open", 100.0, 100.0),
    ("engine.decide", 100.55, 100.56),
    ("chipbench.dispatch:1", 100.6, 100.6),
    ("executor.prefill", 100.7, 101.0),
    ("executor.first_token", 100.9, 101.0),
    ("executor.decode", 101.1, 101.2),
    ("executor.decode", 101.3, 101.4),
    ("engine.decide", 101.45, 101.47),
    ("engine.preempt", 101.47, 101.5),
    ("engine.parked_bytes:3000000000", 101.48, 101.48),
    ("chipbench.dispatch:2", 101.5, 101.5),
    ("executor.decode", 101.6, 101.7),
    ("engine.preempt", 101.9, 102.0),
    ("engine.parked_bytes:1000000000", 101.95, 101.95),
    ("chipbench.dispatch:1", 102.0, 102.0),
    ("executor.decode", 102.2, 102.3),
    ("chipbench.dispatch:4", 103.0, 103.0),
    ("executor.first_token", 103.4, 103.5),
    ("chipbench.dispatch:5", 109.8, 109.8),
    ("executor.first_token", 110.2, 110.3),
    ("engine.parked_bytes:5000000000", 111.0, 111.0),   # after the close
]
DUE = {1: (9, 0.5), 2: (1, 0.0), 3: (9, 8.0), 4: (9, 1.0), 5: (9, 9.5)}


def _window():
    recs = {}
    for rid, (prio, due) in DUE.items():
        req = Request(rid, "t", prio, np.zeros((1, 8), np.int32), 4, due=due)
        recs[rid] = Record(req, sent=1000.0 + due)
    return Window(1000.0, 1010.0, recs, False)


def _reduced(host=HOST):
    return Reduced(open=100.0, close=110.0, runs=[], busy_s=0.0, n_devices=1,
                   host_spans=list(host))


def _run(trace):
    return run.RunData(window=_window(), spec={}, cell={}, mix={}, peaks={},
                       n_chips=1, hi_priority=9, compiles=[], trace=trace)


NEW = {"hi_ttft_p90_s": 2.35, "hi_token_gap_p90_ms": 760.0,
       "engine.decide_ms": 15.0, "engine.preempt_ms": 65.0,
       "engine.parked_state_gb_peak": 3.0}


@pytest.mark.parametrize("name", sorted(NEW))
def test_reader_gives_the_known_answer(name):
    # ttft [0.5, 2.0, 2.5, 0.5] and gaps [0.2, 0.2, 0.9] at p90; decide
    # 10 and 20 ms in the window; preempt 30 and 100 ms; parked 3 GB
    assert reader(ROOT, name)(_run(_reduced())) == pytest.approx(NEW[name])


@pytest.mark.parametrize("name", sorted(NEW))
@pytest.mark.parametrize("trace", ["none", "without program spans"])
def test_reader_gives_none_with_nothing_to_read(name, trace):
    red = (None if trace == "none" else _reduced(
        [h for h in HOST if h[0].startswith("chipbench.")]))
    assert reader(ROOT, name)(_run(red)) is None


def test_spans_take_the_request_last_dispatched_across_a_preemption():
    got = spans.program_spans(_reduced(), _window(), spans.DECODE)
    assert [(sp.rid, round(sp.end, 6)) for sp in got] == [
        (1, 1001.2), (1, 1001.4), (2, 1001.7), (1, 1002.3)]
    assert spans.program_spans(_reduced(), _window(), spans.DECIDE)[0].rid is None
    times = spans.token_times(_reduced(), _window())
    assert times[1] == pytest.approx([1001.0, 1001.2, 1001.4, 1002.3])
    assert times[4] == pytest.approx([1003.5])
    assert times[5] == pytest.approx([1010.3])


def test_parked_peak_keeps_the_level_held_when_the_window_opened():
    host = [("engine.parked_bytes:7000000000", 99.0, 99.0)] + HOST[1:]
    assert spans.parked_peak(_reduced(host)) == 7000000000
    assert spans.parked_peak(_reduced(HOST[1:])) == 3000000000
