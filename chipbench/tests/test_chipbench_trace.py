"""The reduction from a profiler trace to the per-layer metrics' inputs,
on a small trace written out event by event."""
import pytest

from chipbench.harness import counts, trace
from jax.profiler import ProfileData


def _xspace(device_events, host_events):
    """An XSpace in text form: device events on the ``XLA Modules`` line of
    ``/device:TPU:0``, host events on one thread; times in microseconds."""
    def plane(pid, name, line, events):
        names = sorted({n for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = " ".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1e6)} "
            f"duration_ps: {int(d * 1e6)} }}" for n, s, d in events)
        meta = " ".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                        f'name: "{n}" }} }}' for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 0 {evs} }} {meta} }}')
    return ProfileData.from_text_proto(
        plane(1, "/device:TPU:0", "XLA Modules", device_events) + "\n"
        + plane(2, "/host:CPU", "python", host_events))


# request 1 (prompt 128) prefills in 2 periods and decodes twice; request
# 2 (prompt 256) is dispatched and prefills once; 10 us window from t=100
DEVICE = [("jit__period_prefill(1)", 101, 1), ("jit__period_prefill(1)", 102, 1),
          ("jit__decode(2)", 104, 1), ("jit_argmax(5)", 105.5, 0.5),
          ("jit__decode(2)", 107, 1), ("jit__period_prefill(1)", 109, 2)]
HOST = [("chipbench.open", 100, 0), ("chipbench.dispatch:1", 100.5, 0),
        ("PjitFunction(_decode)", 103.5, 0.5), ("chipbench.hold", 106, 0.5),
        ("chipbench.dispatch:2", 108.5, 0)]


@pytest.fixture
def red():
    return trace.reduce(_xspace(DEVICE, HOST), 10e-6)


def test_window_and_busy_time(red):
    assert red.window_s == pytest.approx(10e-6)
    # 1 + 1 + 1 + 0.5 + 1, and 1 of the last run's 2 inside the window
    assert red.busy_s == pytest.approx(5.5e-6)
    assert red.n_devices == 1


def test_runs_named_and_attributed_to_requests(red):
    assert [r.program for r in red.runs] == [
        "_period_prefill", "_period_prefill", "_decode", "argmax", "_decode",
        "_period_prefill"]
    assert [r.rid for r in red.runs] == [1, 1, 1, 1, 1, 2]


def test_step_positions(red):
    got = [(r.program, n) for r, n in trace.step_positions(red, {1: 128, 2: 256})]
    assert got == [("_period_prefill", 128), ("_period_prefill", 128),
                   ("_decode", 128), ("_decode", 129), ("_period_prefill", 256)]


def test_idle_gaps_split_by_what_the_host_did(red):
    gaps = dict(trace.gaps_by_host(red))
    # idle: 100-101, 103-104, 105-105.5, 106-107, 108-109
    assert sum(gaps.values()) == pytest.approx(4.5e-6)
    assert gaps["PjitFunction(_decode)"] == pytest.approx(0.5e-6)
    assert gaps["chipbench.hold"] == pytest.approx(0.5e-6)
    assert gaps["host: untraced"] == pytest.approx(3.5e-6)


def test_device_time_by_program(red):
    got = dict(trace.device_time_by_program(red))
    assert got == pytest.approx({"_period_prefill": 3e-6, "_decode": 2e-6,
                                 "argmax": 0.5e-6})


def test_decode_gap_reader(red):
    from chipbench.harness.catalog import reader
    from chipbench.tests.conftest import ROOT

    class Run:
        trace = red
    gap = reader(ROOT, "executor.decode_gap_ms")(Run)
    # between the two decodes: 2 us apart, 0.5 us of argmax
    assert gap == pytest.approx(1.5e-3)


def test_roofline_reader_never_counts_more_than_needed(red):
    from chipbench.harness.catalog import reader
    from chipbench.tests.conftest import ROOT
    spec = {"hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 4, "intermediate_size": 128,
            "vocab_size": 256, "num_hidden_layers": 2, "model_type": "olmo",
            "tie_word_embeddings": True, "serve_dtype": "bfloat16",
            "reference": "dense"}
    peaks = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}

    class Run:
        trace = red
        prompt_len = {1: 128, 2: 256}
    Run.spec, Run.peaks = spec, peaks
    got = reader(ROOT, "decode_roofline")(Run)
    want = sum(counts.least_time(*counts.decode_step(spec, p), peaks)[0]
               for p in (128, 129)) / 2e-6
    assert got == pytest.approx(100 * want)


def test_reduction_of_a_window_recorded_on_the_chip():
    """Half a second of an olmo-1b.prema_burst window traced on one TPU v5e
    (the program runs of ``/device:TPU:0`` and the host thread holding the
    harness's markers, kept as text)."""
    from chipbench.harness.catalog import reader
    from chipbench.harness.peaks import PEAKS
    from chipbench.tests.conftest import ROOT
    import json
    text = (ROOT / "chipbench/tests/data/v5e_olmo_window.textproto").read_text()
    red = trace.reduce(ProfileData.from_text_proto(text), 0.5)
    assert 0 < red.busy_s < red.window_s == pytest.approx(0.5)
    programs = {r.program for r in red.runs}
    assert {"_decode", "_period_prefill", "_embed"} <= programs
    assert all(r.rid is not None for r in red.runs)
    idle = sum(s for _, s in trace.gaps_by_host(red, top=100))
    assert idle == pytest.approx(red.window_s - red.busy_s, rel=1e-6)

    class Run:
        trace = red
        spec = json.loads((ROOT / "chipbench/configs/olmo-1b.json").read_text())
        peaks = PEAKS["TPU v5 lite"]
        prompt_len = {rid: 128 for rid in range(1000)}
    # the shares as read before the dense family moved out of counts.py
    pinned = {"decode_roofline": 83.68761961266404,
              "period_prefill_roofline": 39.091251137865314}
    for name, want in pinned.items():
        assert reader(ROOT, name)(Run) == pytest.approx(want, rel=1e-12)
    assert reader(ROOT, "executor.decode_step_ms")(Run) > 0
