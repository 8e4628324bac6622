"""Flash-attention + decode-attention kernels vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.decode_attention import decode_attention, decode_attention_ref
from repro.kernels.flash_attention import attention_ref, flash_attention

# Model/kernel execution (real JAX compute): excluded from `make test-fast`.
pytestmark = pytest.mark.slow

CASES = [  # b, hq, hkv, s, t, d, causal
    (2, 4, 2, 128, 128, 64, True),
    (1, 8, 8, 256, 256, 32, True),
    (2, 4, 1, 100, 100, 64, True),      # ragged (padding path)
    (1, 4, 2, 64, 192, 64, False),      # cross-attention shape
    (1, 2, 2, 128, 128, 128, True),
]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_vs_oracle(case, dtype, key):
    b, hq, hkv, s, t, d, causal = case
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, s, d), dtype)
    k = jax.random.normal(ks[1], (b, hkv, t, d), dtype)
    v = jax.random.normal(ks[2], (b, hkv, t, d), dtype)
    out = flash_attention(q, k, v, causal=causal, bq=64, bt=64,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    tol = 3e-2 if dtype == jnp.bfloat16 else 3e-4
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        rtol=tol, atol=tol)


DECODE_CASES = [  # b, hq, hkv, t, d, pos
    (2, 8, 2, 512, 64, 300),
    (1, 4, 4, 1024, 128, 1023),
    (2, 16, 2, 700, 64, 0),
    (1, 32, 4, 4096, 128, 2048),
]


@pytest.mark.parametrize("case", DECODE_CASES)
def test_decode_vs_oracle(case, key):
    b, hq, hkv, t, d, pos = case
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, t, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, t, d), jnp.float32)
    out = decode_attention(q, k, v, jnp.int32(pos), bt=256,
                           interpret=True)
    g = hq // hkv
    ref = decode_attention_ref(q.reshape(b, hkv, g, d), k, v,
                               pos).reshape(b, hq, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)


def test_decode_pos_zero_attends_only_first(key):
    """pos=0 must equal attending to exactly the first cache entry → the
    output is v[:, :, 0] broadcast per head group."""
    b, hq, hkv, t, d = 1, 4, 2, 256, 32
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, hq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, t, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, t, d), jnp.float32)
    out = decode_attention(q, k, v, jnp.int32(0), bt=64, interpret=True)
    expect = jnp.repeat(v[:, :, 0], hq // hkv, axis=1).reshape(b, hq, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


@settings(max_examples=10, deadline=None)
@given(s=st.integers(16, 96), d=st.sampled_from([32, 64]),
       hkv=st.sampled_from([1, 2]), g=st.sampled_from([1, 2, 4]),
       seed=st.integers(0, 2 ** 16))
def test_property_flash_random(s, d, hkv, g, seed):
    kk = jax.random.PRNGKey(seed)
    ks = jax.random.split(kk, 3)
    hq = hkv * g
    q = jax.random.normal(ks[0], (1, hq, s, d), jnp.float32)
    k = jax.random.normal(ks[1], (1, hkv, s, d), jnp.float32)
    v = jax.random.normal(ks[2], (1, hkv, s, d), jnp.float32)
    out = flash_attention(q, k, v, causal=True, bq=32, bt=32,
                          interpret=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-3, atol=1e-3)
