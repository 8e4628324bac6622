"""The seeded traffic: deterministic, at the stated rate, lengths drawn
from the mix's published distributions, and the same work on every seed."""
import json

import numpy as np
import pytest

from chipbench.harness.traffic import (OnOff, Traffic, open_schedule,
                                       quantiles, size_cycle)
from chipbench.tests.conftest import ROOT

MIX = json.loads((ROOT / "chipbench/traffic/prema_burst.json").read_text())
CELL = {"rates_per_s": {"interactive": 3.0}}
SEEDS = (1, 2 ** 31 + 5, 2 ** 33 + 17)


def _traffic(seed, seconds=40.0):
    return Traffic(MIX, CELL, 50304, seed, seconds)


def _open(seed, seconds=40.0):
    return _traffic(seed, seconds).open


def test_same_seed_same_requests():
    a, b = _open(2 ** 31 + 5), _open(2 ** 31 + 5)
    assert [(r.due, r.max_new_tokens) for r in a] == \
        [(r.due, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("seed", SEEDS)
def test_seeds_share_the_work_and_differ_in_tokens(seed):
    base, other = _open(SEEDS[0]), _open(seed)
    assert [(r.due, r.prompt_len, r.max_new_tokens) for r in base] == \
        [(r.due, r.prompt_len, r.max_new_tokens) for r in other]
    if seed != SEEDS[0]:
        assert not any(np.array_equal(x.prompt, y.prompt)
                       for x, y in zip(base, other))


@pytest.mark.parametrize("seed", SEEDS)
def test_lengths_within_the_mix(seed):
    tr = _traffic(seed)
    t = MIX["tenants"][0]
    cycle = tr.cycles["interactive"]
    assert len(cycle) == t["size_cycle"]
    assert [(r.prompt_len, r.max_new_tokens) for r in tr.open] == \
        [cycle[i % len(cycle)] for i in range(len(tr.open))]
    for r in tr.open:
        assert r.prompt_len + r.max_new_tokens <= MIX["max_context"]
        assert r.prompt_len >= t["lengths"]["prompt"]["min"]
        assert r.max_new_tokens >= t["lengths"]["output"]["min"]
        assert 0 <= r.due < 40.0
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 50304


@pytest.mark.parametrize("tenant, side", [(0, "prompt"), (0, "output"),
                                          (1, "prompt"), (1, "output")])
def test_quantiles_keep_the_published_median_and_spread(tenant, side):
    t = MIX["tenants"][tenant]
    dist = t["lengths"][side]
    v = quantiles(dist, t["size_cycle"])
    assert np.all(np.diff(v) >= 0)
    assert np.median(v) == pytest.approx(dist["median"], rel=0.15)
    # the log-lengths spread as the stated sigma, less the tails that k
    # evenly spaced quantiles leave out
    assert np.std(np.log(v)) == pytest.approx(dist["sigma"], rel=0.2)


def test_prompts_are_cut_to_leave_room_for_the_output():
    t = {"size_cycle": 8,
         "lengths": {"prompt": {"median": 10 ** 6, "sigma": 0.1, "min": 4},
                     "output": {"median": 20, "sigma": 1.0, "min": 1}}}
    cycle = size_cycle(t, 100, np.random.default_rng(0))
    assert all(p + o == 100 for p, o in cycle)
    assert len({o for _, o in cycle}) > 4


def test_rate_and_burstiness_over_a_long_horizon():
    src = OnOff.bursty(3.0, duty=0.3, per_burst=20)
    due = open_schedule(MIX["tenants"][0], 3.0, 20000.0, 7)
    assert len(due) / 20000.0 == pytest.approx(3.0, rel=0.05)
    assert src.rate_on == pytest.approx(10.0)
    # bursts: the squared coefficient of variation of the gaps is far
    # above a Poisson stream's 1
    g = np.diff(due)
    assert g.var() / g.mean() ** 2 > 2.0


def test_closed_tenant_cycles_its_sizes():
    tr = _traffic(3, 10.0)
    k = MIX["tenants"][1]["size_cycle"]
    got = [tr.next_closed("batch") for _ in range(2 * k)]
    sizes = [(r.prompt_len, r.max_new_tokens) for r in got]
    assert sizes[:k] == sizes[k:] == tr.cycles["batch"]
    assert len(set(sizes[:k])) == k
    assert len({r.rid for r in got}) == len(got)
    assert all(r.due is None and r.priority == 1 for r in got)


def test_warm_up_shapes_cover_every_size_sent():
    tr = _traffic(5)
    longest = dict(tr.shapes())
    for cycle in tr.cycles.values():
        for plen, n in cycle:
            assert longest[plen] >= n
    assert len(longest) == len({p for c in tr.cycles.values() for p, _ in c})
