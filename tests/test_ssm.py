"""SSD invariants: chunked algorithm == sequential recurrence oracle, and
decode continues prefill exactly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import configs as C
from repro.models.ssm import (init_ssm_params, ssd_chunked, ssd_sequential,
                              ssm_decode, ssm_prefill)


def _mk_inputs(b, l, h, p, g, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, l, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, l, h)) - 1.0)
    a_log = jnp.log(jnp.linspace(1.0, 8.0, h))
    bm = jax.random.normal(ks[2], (b, l, g, n), jnp.float32) * 0.5
    cm = jax.random.normal(ks[3], (b, l, g, n), jnp.float32) * 0.5
    return x, dt, a_log, bm, cm


@pytest.mark.parametrize("chunk", [4, 8, 16])
def test_chunked_equals_sequential(chunk):
    x, dt, a_log, bm, cm = _mk_inputs(2, 32, 4, 8, 1, 16)
    y_c, s_c = ssd_chunked(x, dt, a_log, bm, cm, chunk)
    y_s, s_s = ssd_sequential(x, dt, a_log, bm, cm)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_s),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=10, deadline=None)
@given(b=st.integers(1, 3), nc=st.integers(1, 4), h=st.integers(1, 4),
       p=st.sampled_from([4, 8]), n=st.sampled_from([4, 16]))
def test_chunked_equals_sequential_property(b, nc, h, p, n):
    l = nc * 8
    x, dt, a_log, bm, cm = _mk_inputs(b, l, h, p, 1, n, seed=b + nc * 10)
    y_c, s_c = ssd_chunked(x, dt, a_log, bm, cm, 8)
    y_s, s_s = ssd_sequential(x, dt, a_log, bm, cm)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s),
                               rtol=2e-4, atol=2e-4)


def test_initial_state_threading():
    """chunked(x, h0) == sequential(x, h0) with a warm state."""
    x, dt, a_log, bm, cm = _mk_inputs(2, 16, 2, 4, 1, 8)
    h0 = jax.random.normal(jax.random.PRNGKey(9), (2, 2, 4, 8))
    y_c, s_c = ssd_chunked(x, dt, a_log, bm, cm, 8, h0=h0)
    y_s, s_s = ssd_sequential(x, dt, a_log, bm, cm, h0=h0)
    np.testing.assert_allclose(np.asarray(y_c), np.asarray(y_s),
                               rtol=1e-4, atol=1e-4)


def test_block_decode_continues_prefill():
    """prefill(x[:T]) then decode(x[T]) == prefill(x[:T+1]) last position."""
    cfg = C.smoke_config("mamba2-780m").with_overrides(dtype="float32")
    p = init_ssm_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 17, cfg.d_model),
                          jnp.float32)
    out_full, _ = ssm_prefill(p, x, cfg)              # odd len -> sequential path
    out_pre, cache = ssm_prefill(p, x[:, :16], cfg)   # chunked path
    out_dec, _ = ssm_decode(p, x[:, 16:17], cache, cfg)
    np.testing.assert_allclose(np.asarray(out_dec[:, 0]),
                               np.asarray(out_full[:, 16]),
                               rtol=2e-3, atol=2e-3)
