"""RG-LRU invariants: associative scan == sequential recurrence; decode
continues prefill; gate stability."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs as C
from repro.models.rglru import (init_rglru_params, rglru_block_decode,
                                rglru_block_prefill, rglru_scan, _gates)


@pytest.fixture(scope="module")
def cfg():
    return C.smoke_config("recurrentgemma-9b").with_overrides(dtype="float32")


def sequential_scan(p, x, cfg, h0=None):
    a, u = _gates(p, x, cfg)
    h = (jnp.zeros_like(u[:, 0]) if h0 is None else h0.astype(jnp.float32))
    ys = []
    for t in range(x.shape[1]):
        h = a[:, t] * h + u[:, t]
        ys.append(h)
    return jnp.stack(ys, 1).astype(x.dtype), ys[-1]


def test_associative_scan_equals_sequential(cfg):
    p = init_rglru_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 24, cfg.d_inner),
                          jnp.float32)
    y_fast, h_fast = rglru_scan(p, x, cfg)
    y_seq, h_seq = sequential_scan(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y_fast), np.asarray(y_seq),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(h_fast), np.asarray(h_seq),
                               rtol=1e-5, atol=1e-6)


@settings(max_examples=8, deadline=None)
@given(b=st.integers(1, 3), s=st.integers(2, 16), seed=st.integers(0, 99))
def test_scan_property(b, s, seed):
    cfg = C.smoke_config("recurrentgemma-9b").with_overrides(dtype="float32")
    p = init_rglru_params(jax.random.PRNGKey(seed), cfg)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (b, s, cfg.d_inner),
                          jnp.float32)
    y_fast, _ = rglru_scan(p, x, cfg)
    y_seq, _ = sequential_scan(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y_fast), np.asarray(y_seq),
                               rtol=1e-4, atol=1e-5)


def test_block_decode_continues_prefill(cfg):
    p = init_rglru_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, cfg.d_model),
                          jnp.float32)
    full, _ = rglru_block_prefill(p, x, cfg)
    pre, cache = rglru_block_prefill(p, x[:, :8], cfg)
    dec, _ = rglru_block_decode(p, x[:, 8:9], cache, cfg)
    np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, 8]),
                               rtol=2e-3, atol=2e-3)


def test_recurrence_is_contractive(cfg):
    """|a_t| < 1 elementwise: bounded state for any input (stability)."""
    p = init_rglru_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 32, cfg.d_inner)) * 10
    a, _ = _gates(p, x, cfg)
    assert float(jnp.max(a)) <= 1.0      # == 1.0 only via f32 rounding
    assert float(jnp.mean(a)) < 1.0
    assert float(jnp.min(a)) >= 0.0
