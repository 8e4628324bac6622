"""The plain reference against the program's own forward pass, both in
float32 on the CPU at a small size: where the configuration file states
what the program computes, the two agree to rounding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import catalog, model
from chipbench.reference import dense
from chipbench.tests.conftest import CONFIGS, config, tiny_spec


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_matches_the_program_in_float32(name):
    spec = tiny_spec(config(name), serve_dtype="float32")
    m, cfg = model.build_model(name, spec)
    params = model.make_weights(spec, 2 ** 32 + 9)
    tokens = np.random.default_rng(0).integers(
        0, cfg.vocab_size, 300).astype(np.int32)
    rows = np.arange(250, 300)
    want = catalog.reference(spec).logits(spec, params, tokens, rows)
    with jax.default_matmul_precision("highest"):
        for r in (rows[0], rows[-1]):
            got, _ = m.prefill(params, {"tokens": jnp.asarray(tokens[None, :r + 1])})
            np.testing.assert_allclose(np.asarray(got)[0, -1], want[r - rows[0]],
                                       rtol=1e-4, atol=1e-4)


def test_fp8_control_rounds_weights():
    w = jnp.linspace(-1.0, 1.0, 1001)
    q = dense._q(w, "float8_e4m3fn")
    err = np.abs(np.asarray(q - w))
    assert 0 < err.max() <= 2 ** -4          # 3 mantissa bits
    assert np.asarray(dense._q(w, None) == w).all()
