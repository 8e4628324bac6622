"""The control: the reference computed one precision step below the
configuration's bfloat16 (fp8 weights), put in the program's place, has to
fail the comparison that the program passes.  At the tiny size of these
tests the tiny cell's limit stands between the two on every seed; the
readings at the cells' own sizes on the chip are in PERF.md."""
import pytest

from chipbench import run
from chipbench.harness import catalog, check, model, serve
from chipbench.harness.traffic import Traffic
from chipbench.tests.conftest import TINY_CELL


@pytest.mark.parametrize("seed", [1, 2, 2 ** 33 + 3])
def test_fp8_control_fails_where_the_program_passes(tiny, seed):
    cell = catalog.find(tiny, TINY_CELL)
    m, cfg = model.build_model(cell.config_name, cell.spec)
    params = model.make_weights(cell.spec, seed)
    traffic = Traffic(cell.mix, cell.params, cfg.vocab_size, seed, 1.0)
    run.warm_up(m, params, traffic.shapes())
    w = serve.serve(cell, m, params, traffic, 1.0)
    lim = cell.params["correct"]
    recs = check.sample(list(w.records.values()), seed, lim["sample_tokens"],
                        lim["sample_requests"])
    assert recs
    program = check.widest_gap(cell.spec, params, recs)
    control = check.widest_gap(cell.spec, params, recs,
                               quantize="float8_e4m3fn")
    assert program <= lim["max_logit_gap"] < control, (program, control)
