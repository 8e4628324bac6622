"""Percentiles, censoring at the window's close, and the end-to-end metrics
of a window."""
import numpy as np
import pytest

from chipbench.harness import stats
from chipbench.harness.serve import Record, Window
from chipbench.harness.traffic import Request


@pytest.mark.parametrize("n", [1, 2, 7, 100])
@pytest.mark.parametrize("q", [0, 50, 90, 95, 100])
def test_percentile_is_numpys_linear(n, q):
    v = np.random.default_rng(n).exponential(size=n)
    assert stats.percentile(v, q) == pytest.approx(np.percentile(v, q))


def test_percentile_of_nothing_is_none():
    assert stats.percentile([], 50) is None


def test_censored_at_close():
    lat = stats.censored_latencies([0.0, 1.0, 2.0], [0.5, None, 12.0], 10.0)
    assert lat == [0.5, 9.0, 8.0]


def test_censored_requests_miss_every_limit():
    assert stats.met([0.0, 1.0, 2.0, 3.0], [0.5, None, 12.0, 3.2],
                     [1.0, 100.0, 100.0, 0.1], 10.0) == [True, False, False, False]


def test_isolated_time_of_a_request():
    cell = {"isolated_s": {"128x5": 0.04, "128x6": 0.05}}
    assert stats.isolated_s(cell, 128, 5) == pytest.approx(0.04)
    with pytest.raises(KeyError):
        stats.isolated_s(cell, 128, 7)


def _rec(rid, prio, plen, out, sent, done, start=100.0):
    r = Request(rid, "t", prio, np.zeros((1, plen), np.int32), out,
                due=None if prio == 1 else sent - start)
    return Record(r, sent=sent, complete=done,
                  tokens=None if done is None else np.zeros((1, out), np.int32))


def test_end_to_end_of_a_window():
    mix = {"tenants": [{"priority": 9}, {"priority": 1}]}
    cell = {"sla_scale": 8.0, "isolated_s": {"128x4": 0.1}}
    recs = [_rec(0, 9, 128, 4, 100.0, 100.5),      # 0.5 s, limit 0.8: met
            _rec(1, 9, 128, 4, 101.0, 102.0),      # 1.0 s: missed
            _rec(2, 9, 128, 4, 105.0, None),       # censored at 110: 5 s
            _rec(3, 9, 128, 4, 109.0, 111.0),      # done after close: 1 s
            _rec(4, 1, 128, 100, 100.0, 104.0),    # batch, counts
            _rec(5, 1, 128, 100, 104.0, 112.0)]    # batch, after close
    w = Window(100.0, 110.0, {r.req.rid: r for r in recs}, False)
    out = stats.end_to_end(w, cell, mix)
    assert out["hi_latency_p50_s"] == pytest.approx(1.0)
    assert out["hi_latency_p90_s"] == pytest.approx(
        np.percentile([0.5, 1.0, 5.0, 1.0], 90))
    assert out["hi_sla_share"] == pytest.approx(25.0)
    assert out["batch_tokens_per_s"] == pytest.approx(10.0)
    assert out["hi_tokens_per_s"] == pytest.approx(0.8)      # 2 x 4 tokens
    assert out["n_hi_censored"] == 2


def test_interactive_rate_runs_to_the_end_of_the_wait():
    mix = {"tenants": [{"priority": 9}, {"priority": 1}]}
    cell = {"sla_scale": 8.0, "isolated_s": {"128x4": 0.1}}
    recs = [_rec(0, 9, 128, 4, 100.0, 100.5),
            _rec(1, 9, 128, 4, 105.0, 112.0),      # done in the wait
            _rec(2, 1, 128, 100, 100.0, 104.0),
            _rec(3, 1, 128, 100, 104.0, 111.0)]    # batch past the close
    w = Window(100.0, 110.0, {r.req.rid: r for r in recs}, False,
               drained=112.0)
    out = stats.end_to_end(w, cell, mix)
    assert out["hi_tokens_per_s"] == pytest.approx(8 / 12.0)
    assert out["batch_tokens_per_s"] == pytest.approx(10.0)
    assert out["n_hi_censored"] == 1                   # latencies: the close
    assert out["hi_latency_p90_s"] == pytest.approx(
        np.percentile([0.5, 5.0], 90))
