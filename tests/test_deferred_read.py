"""Decode tokens stay on the device and reach the host one step late: the
tokens are bit-identical to reading each one in the step that made it,
across CHECKPOINT, KILL and an ``eos_id``; one ``_decode`` runs per token;
a steady-state step uploads nothing; and every token is on the host when a
request completes, which then leaves no device state behind."""
import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import get_model
from repro.serving import (EngineConfig, InferenceRequest,
                           PreemptibleExecutor, ServingEngine, executor)

N = 7
PROMPT = np.array([[5, 7, 9, 11, 2, 4, 6, 8]], np.int32)


@pytest.fixture(scope="module")
def olmo(key):
    m = get_model("olmo-1b", tiny=True)
    return m, m.init_params(key)


@pytest.fixture(scope="module")
def ex(olmo):
    return PreemptibleExecutor(*olmo)


def _batch():
    return {"tokens": jnp.asarray(PROMPT)}


def _prefilled(ex, **kw):
    st = ex.start(_batch(), **kw)
    while st.phase == "prefill":
        st = ex.step_prefill(st)
    return st


def _immediate_reference(ex, n=N):
    """Each token read in the step that made it and uploaded again as the
    next step's input, with its position (the read never waits a step)."""
    st = _prefilled(ex, eos_id=-1)
    while len(st.tokens_out) < n:
        st.tok = jnp.asarray(st.tokens_out[-1][:, None])
        st.pos_dev = jnp.int32(st.pos)
        st = ex.step_decode(st)
        assert not st.pending
    return np.stack(st.tokens_out, 1)


def _checkpointed_with_a_token_pending(ex):
    st = _prefilled(ex, max_new_tokens=N)
    for _ in range(3):
        st = ex.step_decode(st)
    assert st.pending and len(st.tokens_out) == 3
    st = PreemptibleExecutor.restore(PreemptibleExecutor.checkpoint(st))
    assert not st.pending and len(st.tokens_out) == 4
    while st.n_tokens < N:
        st = ex.step_decode(st)
    return st


def _killed_and_restarted(ex):
    st = _prefilled(ex, max_new_tokens=N)
    for _ in range(3):
        st = ex.step_decode(st)
    assert st.pending
    # KILL drops the state, its pending token with it; the request starts
    # again from its prompt
    return ex.run_uninterrupted(_batch(), N)


def _uninterrupted_reading_late(ex):
    st = _prefilled(ex, max_new_tokens=N)
    for k in range(1, N - 1):
        st = ex.step_decode(st)
        # the token of step k waits on the device; the one before is read
        assert st.pending and len(st.tokens_out) == k
    st = ex.step_decode(st)
    assert not st.pending and len(st.tokens_out) == N
    return st


RUNS = {
    "run_uninterrupted": lambda ex: ex.run_uninterrupted(_batch(), N),
    "stepped": _uninterrupted_reading_late,
    "checkpoint_pending": _checkpointed_with_a_token_pending,
    "kill": _killed_and_restarted,
}


@pytest.mark.parametrize("how", sorted(RUNS))
def test_tokens_bit_identical_to_immediate_reads(ex, how):
    st = RUNS[how](ex)
    assert not st.pending
    assert np.array_equal(np.stack(st.tokens_out, 1), _immediate_reference(ex))


def test_eos_request_reads_every_token_in_its_own_step(ex):
    ref = _immediate_reference(ex)
    eos = int(ref[0, 4])
    stop = int(np.argmax(ref[0] == eos))      # its first occurrence
    st = _prefilled(ex, max_new_tokens=N, eos_id=eos)
    while st.n_tokens < N and st.tokens_out[-1][0] != eos:
        st = ex.step_decode(st)
        assert not st.pending
    assert np.array_equal(np.stack(st.tokens_out, 1), ref[:, :stop + 1])
    got = ex.run_uninterrupted(_batch(), N, eos_id=eos)
    assert np.array_equal(np.stack(got.tokens_out, 1), ref[:, :stop + 1])


def test_one_decode_program_run_per_token(ex, monkeypatch):
    runs = []
    decode = executor._decode

    def counted(*args, **kw):
        runs.append(1)
        return decode(*args, **kw)
    monkeypatch.setattr(executor, "_decode", counted)
    st = ex.run_uninterrupted(_batch(), N)
    assert len(st.tokens_out) == N
    assert len(runs) == N - 1


def test_steady_state_decode_uploads_nothing(ex):
    st = _prefilled(ex, max_new_tokens=64)
    st = ex.step_decode(st)          # grows the cache past the prompt
    cap = st.cache["slot0"]["k"].shape[2]
    with jax.transfer_guard_host_to_device("disallow"):
        for _ in range(5):
            st = ex.step_decode(st)
    assert st.cache["slot0"]["k"].shape[2] == cap
    assert st.pending and st.n_tokens == 7


def _engine(olmo, mechanism, **kw):
    return ServingEngine({"olmo-1b": olmo}, cfg=EngineConfig(
        policy="prema", mechanism=mechanism, **kw))


def _requests():
    """A long batch request, preempted in its decode by three short
    interactive ones."""
    rng = np.random.default_rng(11)
    reqs = [InferenceRequest(
        rid=0, arch="olmo-1b", max_new_tokens=12, priority=1, arrival=0.0,
        prompt=rng.integers(1, 200, (1, 10)).astype(np.int32))]
    for i in range(1, 4):
        reqs.append(InferenceRequest(
            rid=i, arch="olmo-1b", max_new_tokens=2 + i, priority=9,
            arrival=1e-5 * i,
            prompt=rng.integers(1, 200, (1, 4 + 2 * i)).astype(np.int32)))
    return reqs


@pytest.mark.parametrize("mechanism,slots", [("checkpoint", 1), ("kill", 1),
                                             ("checkpoint", 2)])
def test_engine_tokens_equal_run_uninterrupted(olmo, mechanism, slots,
                                               monkeypatch):
    pending_at_checkpoint = []
    checkpoint = PreemptibleExecutor.checkpoint

    def ckpt(st):
        pending_at_checkpoint.append(st.pending)
        return checkpoint(st)
    monkeypatch.setattr(PreemptibleExecutor, "checkpoint", staticmethod(ckpt))
    eng = _engine(olmo, mechanism, batch_slots=slots)
    reqs = _requests()
    results = eng.run(reqs)
    assert sorted(r.rid for r in results) == [r.rid for r in reqs]
    if slots == 1:
        assert any(r.n_preemptions + r.n_kills for r in results)
    if mechanism == "checkpoint" and slots == 1:
        assert any(pending_at_checkpoint)
    ex = PreemptibleExecutor(*olmo)
    for r in results:
        req = reqs[r.rid]
        assert r.tokens.shape[1] == req.max_new_tokens
        iso = ex.run_uninterrupted({"tokens": jnp.asarray(req.prompt)},
                                   req.max_new_tokens)
        assert np.array_equal(np.stack(iso.tokens_out, 1), r.tokens), r.rid


def test_token_altered_after_step_decode_changes_served_tokens(
        olmo, monkeypatch):
    plain = _engine(olmo, "checkpoint").run(_requests())
    step = PreemptibleExecutor.step_decode

    def altered(self, st):
        st = step(self, st)
        st.tokens_out[-1] = (st.tokens_out[-1] + 1) % self.cfg.vocab_size
        return st
    monkeypatch.setattr(PreemptibleExecutor, "step_decode", altered)
    served = _engine(olmo, "checkpoint").run(_requests())
    want = {r.rid: r.tokens for r in plain}
    changed = [r.rid for r in served
               if not np.array_equal(r.tokens, want[r.rid])]
    assert changed == [r.rid for r in served]


@pytest.mark.parametrize("slots", [1, 2])
def test_completed_requests_leave_no_device_state(olmo, slots, monkeypatch):
    states = []
    start = PreemptibleExecutor.start

    def tracked(self, *args, **kw):
        st = start(self, *args, **kw)
        states.append(weakref.ref(st))
        return st
    monkeypatch.setattr(PreemptibleExecutor, "start", tracked)
    eng = _engine(olmo, "checkpoint", batch_slots=slots)
    live = []

    def on_complete(ev):
        gc.collect()
        live.append(sum(ref() is not None for ref in states))
    eng.events.subscribe_map({"complete": on_complete})
    reqs = _requests()
    eng.run(reqs)
    # at each completion only requests still unfinished hold a state
    assert len(live) == len(reqs) and live[-1] == 0
    assert all(n <= len(reqs) - k for k, n in enumerate(live, 1))
