"""Per-kernel validation: shape/dtype sweeps against the ref.py oracles,
executed with interpret=True on CPU (deliverable c)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import dynquant, qmatmul, quantize, ref

SHAPES = [(128, 512, 128), (64, 300, 96), (256, 1024, 512), (7, 48, 33),
          (1, 128, 256), (130, 257, 129)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _mk(m, k, n, dtype, seed=0):
    kx, kw = jax.random.split(jax.random.PRNGKey(seed))
    x = (jax.random.normal(kx, (m, k), jnp.float32) * 2).astype(dtype)
    w = jax.random.normal(kw, (k, n), jnp.float32)
    w_i8, w_s = ref.quantize_ref(w)
    return x, w, w_i8, w_s


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_qmatmul_static_matches_ref(shape, dtype):
    m, k, n = shape
    x, w, w_i8, w_s = _mk(m, k, n, dtype)
    a_scale = jnp.max(jnp.abs(x.astype(jnp.float32))) / 127.0
    y_ref = ref.qmatmul_static_ref(x.astype(jnp.float32), w_i8, w_s, a_scale)
    y = qmatmul.qmatmul_static(x, w_i8, w_s, a_scale, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_qmatmul_dynamic_matches_ref(shape, dtype):
    m, k, n = shape
    x, w, w_i8, w_s = _mk(m, k, n, dtype)
    y_ref = ref.qmatmul_dynamic_ref(x.astype(jnp.float32), w_i8, w_s)
    y = dynquant.qmatmul_dynamic(x, w_i8, w_s, interpret=True)
    # bf16 inputs often put x/scale exactly on .5 rounding boundaries; XLA's
    # divide vs reciprocal-multiply then flips a handful of int8 steps per
    # row (~1 ulp upstream). Bound elementwise by a few quantization steps
    # plus 2% relative — catches logic bugs (wrong scale/row/block) while
    # tolerating boundary flips.
    a_scale = np.maximum(
        np.abs(np.asarray(x, np.float32)).max(1, keepdims=True), 1e-12) / 127.0
    step = a_scale * np.abs(np.asarray(w_s))          # [M,1]*[1,N] -> [M,N]
    diff = np.abs(np.asarray(y) - np.asarray(y_ref))
    tol = 8.0 * step + 0.02 * np.abs(np.asarray(y_ref)) + 1e-5
    assert np.all(diff <= tol), float((diff / np.maximum(step, 1e-12)).max())


@pytest.mark.parametrize("shape", [(64, 64), (300, 96), (1024, 512), (48, 33)])
def test_quantize_weights_matches_ref(shape):
    w = jax.random.normal(jax.random.PRNGKey(1), shape, jnp.float32) * 3
    q_ref, s_ref = ref.quantize_ref(w)
    q, s = quantize.quantize_weights(w, interpret=True)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(q_ref))
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6)


def test_quantized_matmul_close_to_fp32():
    x, w, w_i8, w_s = _mk(128, 1024, 256, jnp.float32)
    y = dynquant.qmatmul_dynamic(x, w_i8, w_s, interpret=True)
    y_fp = x @ w
    rel = float(jnp.linalg.norm(y - y_fp) / jnp.linalg.norm(y_fp))
    assert rel < 0.03, f"int8 quantization error too large: {rel}"


@settings(max_examples=20, deadline=None)
@given(m=st.integers(1, 64), k=st.integers(8, 256), n=st.integers(1, 64),
       scale=st.floats(0.01, 100.0))
def test_dynamic_kernel_property(m, k, n, scale):
    """Property: kernel == oracle for arbitrary shapes/magnitudes."""
    x, w, w_i8, w_s = _mk(m, k, n, jnp.float32, seed=m * 1000 + k * 10 + n)
    x = x * scale
    y_ref = ref.qmatmul_dynamic_ref(x, w_i8, w_s)
    y = dynquant.qmatmul_dynamic(x, w_i8, w_s, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4 * scale)


@pytest.mark.parametrize("dims", [(2, 2, 4, 32, 64), (1, 8, 4, 128, 256),
                                  (3, 1, 16, 64, 48)])
def test_qdecode_matches_ref(dims):
    """int8-KV decode attention kernel vs oracle (fused dequant)."""
    from repro.kernels import qdecode

    b, hkv, g, hd, s = dims
    ks = jax.random.split(jax.random.PRNGKey(sum(dims)), 3)
    q = jax.random.normal(ks[0], (b, hkv, g, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, hd), jnp.float32)
    k_i8, k_s = ref.quantize_kv_ref(k)
    v_i8, v_s = ref.quantize_kv_ref(v)
    bias = jnp.where(jnp.arange(s) < s - 5, 0.0, -2e38)
    bias = jnp.broadcast_to(bias[None], (b, s)).astype(jnp.float32)
    y_ref = ref.qdecode_ref(q, k_i8, k_s, v_i8, v_s, bias)
    y = qdecode.qdecode_attention(q, k_i8, k_s, v_i8, v_s, bias,
                                  interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


def test_qdecode_close_to_fp_attention():
    """int8-KV attention stays within quantization error of fp attention."""
    from repro.kernels import qdecode

    b, hkv, g, hd, s = 2, 2, 4, 64, 128
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (b, hkv, g, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, hkv, hd), jnp.float32)
    k_i8, k_s = ref.quantize_kv_ref(k)
    v_i8, v_s = ref.quantize_kv_ref(v)
    bias = jnp.zeros((b, s), jnp.float32)
    y = qdecode.qdecode_attention(q, k_i8, k_s, v_i8, v_s, bias,
                                  interpret=True)
    # fp reference
    scores = jnp.einsum("bkgh,bskh->bkgs", q, k) / jnp.sqrt(hd)
    p = jax.nn.softmax(scores, -1)
    y_fp = jnp.einsum("bkgs,bskh->bkgh", p, v)
    rel = float(jnp.linalg.norm(y - y_fp) / jnp.linalg.norm(y_fp))
    assert rel < 0.02, rel
