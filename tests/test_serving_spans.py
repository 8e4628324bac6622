"""The serving path's wall-clock spans, read back from a profiler trace
recorded on the CPU: their names and nesting, one span per step and per
preemption, the parked-bytes gauge, and none at all in virtual mode."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.models import get_model
from repro.serving import (EngineConfig, InferenceRequest,
                           PreemptibleExecutor, ServingEngine)
from repro.serving.engine import PARKED_BYTES

pytestmark = pytest.mark.slow

ENGINE = {"engine.iteration", "engine.ingest", "engine.decide",
          "engine.preempt", "engine.begin", "engine.complete"}
EXECUTOR = {"executor.prefill", "executor.first_token", "executor.decode",
            "executor.sync", "executor.checkpoint"}


class _Stop(Exception):
    pass


@pytest.fixture(scope="module")
def olmo(key):
    m = get_model("olmo-1b", tiny=True)
    return {"olmo-1b": (m, m.init_params(key))}


def _requests(n=3, max_new=5):
    rng = np.random.default_rng(5)
    return [InferenceRequest(
        rid=i, arch="olmo-1b",
        prompt=rng.integers(1, 200, (1, 6 + 3 * i)).astype(np.int32),
        max_new_tokens=max_new, priority=1 + 4 * (i % 3), arrival=0.0)
        for i in range(n)]


def _program_spans(tmp_path, fn):
    """Run ``fn`` under a profiler trace; the program's spans on the host,
    as ``(name, start_ns, end_ns, args)`` in start order."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    except _Stop:
        pass
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "executor.")):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda s: s[1])


def _engine(models, **kw):
    return ServingEngine(models, cfg=EngineConfig(
        policy="rrb", preemptive=True, mechanism="checkpoint", **kw))


def test_spans_nest_and_count_steps_and_preemptions(olmo, tmp_path):
    eng = _engine(olmo)
    preempts = []
    eng.events.subscribe_map({"preempt": lambda ev: preempts.append(ev.tid)})
    reqs = _requests()
    spans = _program_spans(tmp_path, lambda: eng.run(reqs))
    names = [n for n, _, _, _ in spans]
    assert ENGINE | EXECUTOR <= set(names)
    assert preempts, "the round-robin policy preempts at step boundaries"
    assert names.count("engine.preempt") == len(preempts)
    assert names.count("executor.checkpoint") == len(preempts)
    n_periods = olmo["olmo-1b"][0].cfg.n_periods
    assert names.count("executor.prefill") == len(reqs) * n_periods
    assert names.count("executor.first_token") == len(reqs)
    n_tokens = sum(r.tokens.shape[1] for r in eng.completed)
    assert names.count("executor.decode") == n_tokens - len(reqs)
    # every decoded token is read once: late, or waiting on its own step
    assert (names.count("executor.sync") + names.count("executor.read_deferred")
            == n_tokens - len(reqs))
    assert names.count("engine.complete") == len(reqs)
    # every span but the gauge lies inside one pass of the main loop
    loops = [(s, e) for n, s, e, _ in spans if n == "engine.iteration"]
    for n, s, e, _ in spans:
        if not n.startswith(PARKED_BYTES):
            assert any(ls <= s and e <= le for ls, le in loops), n
    begun = [a["rid"] for n, _, _, a in spans if n == "engine.begin"]
    assert set(begun) == {r.rid for r in reqs}
    # a first token ends inside its prefill period; a late read inside the
    # next decode; a waiting read inside a decode, a checkpoint or a
    # completion
    for inner, outer in (("executor.first_token", {"executor.prefill"}),
                         ("executor.read_deferred", {"executor.decode"}),
                         ("executor.sync", {"executor.decode",
                                            "executor.checkpoint",
                                            "engine.complete"})):
        hosts = [(s, e) for n, s, e, _ in spans if n in outer]
        for n, s, e, _ in spans:
            if n == inner:
                assert any(hs <= s and e <= he for hs, he in hosts), n


def test_parked_gauge_is_the_bytes_preempted_requests_hold(
        olmo, tmp_path, monkeypatch):
    held = {}
    checkpoint, restore = PreemptibleExecutor.checkpoint, PreemptibleExecutor.restore

    def ckpt(st):
        held[id(st)] = st
        return checkpoint(st)

    def rest(st):
        held.pop(id(st), None)
        return restore(st)
    monkeypatch.setattr(PreemptibleExecutor, "checkpoint", staticmethod(ckpt))
    monkeypatch.setattr(PreemptibleExecutor, "restore", staticmethod(rest))
    eng = _engine(olmo)
    preempted = set()

    def on_dispatch(ev):
        # stop as a preempted request resumes while others stay parked
        if ev.tid in preempted and held:
            raise _Stop

    eng.events.subscribe_map({"preempt": lambda ev: preempted.add(ev.tid),
                              "dispatch": on_dispatch})
    spans = _program_spans(tmp_path, lambda: eng.run(_requests(n=4)))
    gauges = [int(n[len(PARKED_BYTES):]) for n, _, _, _ in spans
              if n.startswith(PARKED_BYTES)]
    assert gauges[0] == 0 and len(gauges) > 2
    want = sum(st.device_bytes() for st in held.values())
    assert want > 0
    assert gauges[-1] == want


def test_batched_path_spans_carry_the_slot(olmo, tmp_path):
    eng = _engine(olmo, batch_slots=2)
    reqs = _requests(n=4)
    spans = _program_spans(tmp_path, lambda: eng.run(reqs))
    names = {n for n, _, _, _ in spans}
    assert {"engine.iteration", "engine.ingest", "engine.decide",
            "engine.begin", "engine.complete", "executor.first_token",
            "executor.decode"} <= names
    slots = {a["slot"] for n, _, _, a in spans if n == "engine.begin"}
    assert slots == {0, 1}
    assert sum(n == "executor.first_token" for n, _, _, _ in spans) == len(reqs)


def test_virtual_mode_records_no_program_span(olmo, tmp_path):
    eng = _engine(olmo, execute=False)
    spans = _program_spans(tmp_path, lambda: eng.run(_requests()))
    assert len(eng.completed) == 3
    assert spans == []


def test_device_bytes_counts_every_array_the_state_holds(olmo):
    model, params = olmo["olmo-1b"]
    ex = PreemptibleExecutor(model, params)
    st = ex.start({"tokens": jnp.zeros((1, 8), jnp.int32)})

    def arrays(st):
        leaves = jax.tree.leaves((st.h, st.img_h, st.cache_slices, st.cache,
                                  st.last_logits, st.tok, st.pos_dev))
        return sum(a.nbytes for a in leaves)
    while st.phase == "prefill":
        st = ex.step(st)
        assert st.device_bytes() == arrays(st)
        assert st.device_bytes() >= st.context_bytes() + st.cache_bytes()
    for _ in range(3):
        st = ex.step(st)
    assert st.device_bytes() == arrays(st)
    assert st.device_bytes() == (st.context_bytes() + st.cache_bytes()
                                 + (st.img_h.nbytes if st.img_h is not None
                                    else 0))


def test_late_reads_are_spanned_once_per_token(olmo, tmp_path):
    # one request alone: every decoded token but the last is read one step
    # late, inside the next step's decode span; the last in its own step
    eng = ServingEngine(olmo, cfg=EngineConfig(policy="fcfs"))
    reqs = _requests(n=1, max_new=6)
    spans = _program_spans(tmp_path, lambda: eng.run(reqs))
    names = [n for n, _, _, _ in spans]
    assert names.count("executor.decode") == 5
    assert names.count("executor.read_deferred") == 4
    assert names.count("executor.sync") == 1
    decodes = [(s, e) for n, s, e, _ in spans if n == "executor.decode"]
    reads = [(s, e) for n, s, e, _ in spans
             if n in ("executor.read_deferred", "executor.sync")]
    # the first decode reads nothing; each later one reads one token
    for (ds, de), (rs, re_) in zip(decodes[1:], reads):
        assert ds <= rs and re_ <= de
