"""Preemptible-matmul kernel: shape/dtype sweeps vs the jnp oracle, and the
checkpoint/resume contract (the paper's ACCQ semantics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.preemptible_matmul import (advance, finish, matmul,
                                              matmul_partial_ref, matmul_ref,
                                              start)

# Model/kernel execution (real JAX compute): excluded from `make test-fast`.
pytestmark = pytest.mark.slow

SHAPES = [(128, 128, 128), (256, 384, 512), (100, 200, 300), (64, 1000, 72),
          (1, 129, 1), (257, 64, 130)]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_matches_oracle(shape, dtype, key):
    m, k, n = shape
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (m, k), dtype)
    y = jax.random.normal(k2, (k, n), dtype)
    out = matmul(x, y, out_dtype=jnp.float32, interpret=True)
    ref = matmul_ref(x, y, out_dtype=jnp.float32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_resume_equals_uninterrupted_bitwise(key):
    """CHECKPOINT contract: any interleaving of advance() calls yields the
    *bit-identical* accumulator as one uninterrupted run."""
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (256, 640, ), jnp.float32).reshape(256, 640)
    y = jax.random.normal(k2, (640, 256), jnp.float32)
    one = start(x, y)
    one = advance(one, x, y, n_tiles=one.n_ktiles, interpret=True)
    ref = finish(one)

    chunked = start(x, y)
    for step in (1, 2, 1, 1):  # arbitrary preemption pattern
        chunked = advance(chunked, x, y, n_tiles=step, interpret=True)
    out = finish(chunked)
    assert np.array_equal(np.asarray(out), np.asarray(ref))


def test_partial_accumulator_matches_partial_ref(key):
    k1, k2 = jax.random.split(key)
    x = jax.random.normal(k1, (128, 512), jnp.float32)
    y = jax.random.normal(k2, (512, 128), jnp.float32)
    ck = start(x, y)
    ck = advance(ck, x, y, n_tiles=2, interpret=True)  # K tiles [0, 2)
    ref = matmul_partial_ref(x, y, jnp.zeros((128, 128), jnp.float32), 0, 2)
    np.testing.assert_allclose(np.asarray(ck.acc[:128, :128]),
                               np.asarray(ref), rtol=1e-4, atol=1e-4)
    assert not ck.done and ck.k_tile == 2


def test_checkpoint_bytes_is_accumulator_size(key):
    x = jnp.ones((256, 256)); y = jnp.ones((256, 512))
    ck = start(x, y)
    assert ck.context_bytes() == 256 * 512 * 4


@settings(max_examples=15, deadline=None)
@given(m=st.integers(1, 200), k=st.integers(1, 300), n=st.integers(1, 200),
       seed=st.integers(0, 2 ** 16))
def test_property_random_shapes(m, k, n, seed):
    kk = jax.random.PRNGKey(seed)
    k1, k2 = jax.random.split(kk)
    x = jax.random.normal(k1, (m, k), jnp.float32)
    y = jax.random.normal(k2, (k, n), jnp.float32)
    out = matmul(x, y, out_dtype=jnp.float32, interpret=True)
    ref = matmul_ref(x, y, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
