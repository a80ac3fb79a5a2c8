"""Property tests for the quantization core (hypothesis) + calibration flow."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from conftest import make_batch
from repro import configs as C
from repro.core.quant import (CalibrationSession, QuantConfig,
                              dequantize_tensor, quantize_tensor,
                              quantize_tree, tree_size_bytes)
from repro.models import forward, init_params


@settings(max_examples=30, deadline=None)
@given(rows=st.integers(1, 32), cols=st.integers(1, 64),
       mag=st.floats(1e-3, 1e3), symmetric=st.booleans(),
       per_channel=st.booleans())
def test_quantize_roundtrip_error_bound(rows, cols, mag, symmetric, per_channel):
    """|x - dequant(quant(x))| <= scale/2 elementwise (round-to-nearest)."""
    x = np.random.default_rng(rows * 100 + cols).normal(
        size=(rows, cols)).astype(np.float32) * mag
    q = quantize_tensor(jnp.asarray(x), per_channel=per_channel,
                        symmetric=symmetric)
    dq = np.asarray(dequantize_tensor(q))
    scale = np.broadcast_to(np.asarray(q["scale"]), x.shape)
    # 0.505: reciprocal-multiply quantization (see kernels/ref.py) can round
    # one f32-ulp past the exact nearest-step boundary
    assert np.all(np.abs(x - dq) <= scale * 0.505 + 1e-6 * mag)


@settings(max_examples=20, deadline=None)
@given(mag=st.floats(1e-3, 1e3))
def test_quantize_scale_invariance(mag):
    """quant is scale-equivariant: q(a*x).w_int8 == q(x).w_int8."""
    x = np.random.default_rng(0).normal(size=(16, 16)).astype(np.float32)
    q1 = quantize_tensor(jnp.asarray(x))
    q2 = quantize_tensor(jnp.asarray(x * mag))
    np.testing.assert_array_equal(np.asarray(q1["w_int8"]),
                                  np.asarray(q2["w_int8"]))


def test_stacked_leaves_keep_layer_dim():
    w = jnp.ones((3, 8, 16))  # [L, K, N]
    q = quantize_tensor(w)
    assert q["scale"].shape == (3, 1, 16)
    q = quantize_tensor(w, per_channel=False)
    assert q["scale"].shape == (3, 1, 1)


def test_quantize_tree_excludes_sensitive_leaves():
    cfg = C.smoke_config("recurrentgemma-9b").with_overrides(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    qp, paths = quantize_tree(params, QuantConfig(mode="dynamic_int8",
                                                  min_size=256))
    assert paths, "nothing was quantized"
    assert not any("lam" in p or "conv_w" in p for p in paths)
    # norms untouched
    assert not any(p.endswith(("ln1", "ln2", "final_norm")) for p in paths)


def test_size_reduction_approaches_4x_at_scale():
    """The paper's ~4x claim holds once matmul weights dominate."""
    cfg = C.smoke_config("stablelm-1.6b").with_overrides(
        dtype="float32", d_model=256, d_ff=1024, n_layers=3, vocab_size=512)
    params = init_params(jax.random.PRNGKey(0), cfg)
    qp, _ = quantize_tree(params, QuantConfig(mode="dynamic_int8",
                                              min_size=1024))
    ratio = tree_size_bytes(params) / tree_size_bytes(qp)
    assert ratio > 3.0, f"expected near-4x size reduction, got {ratio:.2f}"


def test_static_calibration_end_to_end():
    cfg = C.smoke_config("phi3-mini-3.8b").with_overrides(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    qc = QuantConfig(mode="static_int8", min_size=1024)
    sess = CalibrationSession(params, qc)
    for i in range(2):
        jax.block_until_ready(
            forward(sess.instrumented_params, make_batch(cfg, seed=i), cfg)[0])
    scales = sess.act_scales()
    assert scales, "calibration recorded nothing"
    qp, paths = quantize_tree(params, qc, scales)
    n_static = 0
    def count(leaf):
        nonlocal n_static
        if isinstance(leaf, dict) and "act_scale" in leaf:
            n_static += 1
        return leaf
    jax.tree.map(count, qp,
                 is_leaf=lambda x: isinstance(x, dict) and "w_int8" in x)
    assert n_static > 0
    logits_fp, _ = forward(params, make_batch(cfg, seed=5), cfg)
    logits_q, _ = forward(qp, make_batch(cfg, seed=5), cfg)
    cos = float(jnp.sum(logits_fp * logits_q) /
                (jnp.linalg.norm(logits_fp) * jnp.linalg.norm(logits_q)))
    assert cos > 0.98, f"static-int8 model diverged: cos={cos}"


def test_per_layer_static_scales_for_stacked_params():
    cfg = C.smoke_config("phi3-mini-3.8b").with_overrides(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    qc = QuantConfig(mode="static_int8", min_size=1024)
    sess = CalibrationSession(params, qc)
    jax.block_until_ready(
        forward(sess.instrumented_params, make_batch(cfg), cfg)[0])
    scales = sess.act_scales()
    stacked = [v for k, v in scales.items() if k.startswith("layers/")]
    assert stacked and all(isinstance(v, list) and len(v) == cfg.n_layers
                           for v in stacked)


@pytest.mark.parametrize("bits,granularity,group", [
    (8, "per_group", 16), (4, "per_channel", 0), (4, "per_group", 16)])
def test_advanced_quant_modes_roundtrip(bits, granularity, group):
    """int4 / per-group (paper 'future work'): bound still holds per group."""
    x = np.random.default_rng(0).normal(size=(64, 32)).astype(np.float32)
    q = quantize_tensor(jnp.asarray(x), bits=bits,
                        group_size=group if granularity == "per_group" else 0)
    dq = np.asarray(dequantize_tensor(q))
    key = "w_int4" if bits == 4 else "w_int8"
    assert key in q
    scale = np.asarray(q["scale"])
    if scale.ndim == 3:   # grouped: broadcast scale back over groups
        g = x.shape[0] // scale.shape[0]
        scale = np.repeat(scale, g, axis=0).reshape(x.shape[0], x.shape[1])
    else:
        scale = np.broadcast_to(scale, x.shape)
    assert np.all(np.abs(x - dq) <= scale * 0.505 + 1e-6)


def test_advanced_quant_model_end_to_end():
    cfg = C.smoke_config("phi3-mini-3.8b").with_overrides(dtype="float32")
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = make_batch(cfg)
    ref, _ = forward(params, batch, cfg)
    qp, paths = quantize_tree(params, QuantConfig(
        "dynamic_int8", granularity="per_group", group_size=32, min_size=1024))
    out, _ = forward(qp, batch, cfg)
    cos = float(jnp.sum(ref * out) /
                (jnp.linalg.norm(ref) * jnp.linalg.norm(out)))
    assert cos > 0.995, cos
    # int4 halves the artifact again vs int8
    qp8, _ = quantize_tree(params, QuantConfig("dynamic_int8", min_size=1024))
    qp4, _ = quantize_tree(params, QuantConfig("dynamic_int8", bits=4,
                                               min_size=1024))
    assert tree_size_bytes(qp4) < 0.62 * tree_size_bytes(qp8)
