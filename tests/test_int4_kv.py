"""int4 KV tier unit + property tests: the nibble wire layout
(pack/unpack roundtrip), grouped quantize->dequantize error bounds
(hypothesis via the compat shim), precision-tier config resolution, and
end-to-end greedy argmax stability of the int4 engine vs fp32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import given, settings, strategies as st
from repro import configs as C
from repro.api import ModelArtifact
from repro.kernels.quantize import (KV_GROUP, dequantize_kv_int4,
                                    kv_group_size, pack_int4,
                                    quantize_kv_int4, unpack_int4)
from repro.models import init_params, prefill
from repro.serving import ContinuousBatchingEngine


# ------------------------------------------------------------------ #
# Wire layout: pack/unpack
# ------------------------------------------------------------------ #
@settings(max_examples=30, deadline=None)
@given(rows=st.integers(1, 16), cols=st.integers(1, 32), seed=st.integers(0, 8))
def test_pack_unpack_roundtrip(rows, cols, seed):
    """unpack(pack(codes)) == codes for every signed-4-bit code, any shape
    with an even trailing dim."""
    rng = np.random.default_rng(seed)
    codes = rng.integers(-8, 8, size=(rows, 2 * cols)).astype(np.int8)
    packed = pack_int4(jnp.asarray(codes))
    assert packed.shape == (rows, cols) and packed.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(unpack_int4(packed)), codes)


def test_pack_layout_is_low_nibble_even():
    """Element d lives in byte d // 2, even index in the LOW nibble — the
    exact layout the Pallas kernels unpack in-VMEM."""
    codes = jnp.asarray([[3, -5, 7, -8]], jnp.int8)
    packed = np.asarray(pack_int4(codes)).astype(np.uint8)
    assert packed[0, 0] & 0xF == 3
    assert (packed[0, 0] >> 4) & 0xF == (-5) & 0xF
    assert packed[0, 1] & 0xF == 7
    assert (packed[0, 1] >> 4) & 0xF == (-8) & 0xF


# ------------------------------------------------------------------ #
# Grouped quantization: error bound + shapes
# ------------------------------------------------------------------ #
@settings(max_examples=30, deadline=None)
@given(rows=st.integers(1, 24), hd=st.sampled_from([16, 32, 64, 128]),
       mag=st.floats(1e-3, 1e3))
def test_quantize_dequantize_error_bound(rows, hd, mag):
    """|x - dq(q(x))| <= ~scale / 2 elementwise per group: codes are rounded
    against the STORED f16 scale, so dequantization reconstructs to within
    half a step (plus one f32 division ulp at rounding boundaries)."""
    x = np.random.default_rng(rows * 1000 + hd).normal(
        size=(rows, hd)).astype(np.float32) * mag
    x_i4, x_s = quantize_kv_int4(jnp.asarray(x))
    assert x_i4.shape == (rows, hd // 2) and x_i4.dtype == jnp.int8
    g = kv_group_size(hd)
    assert x_s.shape == (rows, hd // g) and x_s.dtype == jnp.float16
    dq = np.asarray(dequantize_kv_int4(x_i4, x_s))
    bound = np.repeat(np.asarray(x_s, np.float32), g, axis=-1)
    assert np.all(np.abs(x - dq) <= bound * 0.505 + 1e-6 * mag)


def test_group_size_clamps_to_head_dim():
    assert kv_group_size(256) == KV_GROUP
    assert kv_group_size(KV_GROUP) == KV_GROUP
    assert kv_group_size(16) == 16          # hd < KV_GROUP: one group


def test_quantize_explicit_group_size():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
    _, s8 = quantize_kv_int4(x, group_size=8)
    assert s8.shape == (4, 8)
    # finer groups reconstruct at least as well as the default
    d8 = dequantize_kv_int4(quantize_kv_int4(x, group_size=8)[0], s8)
    d32 = dequantize_kv_int4(*quantize_kv_int4(x))
    assert float(jnp.abs(x - d8).max()) <= float(jnp.abs(x - d32).max()) + 1e-6


# ------------------------------------------------------------------ #
# Precision-tier config resolution
# ------------------------------------------------------------------ #
def test_kv_precision_resolution_and_validation():
    cfg = C.smoke_config("mistral-nemo-12b")
    assert cfg.kv_precision == "fp"
    assert cfg.with_overrides(kv_cache_int8=True).kv_precision == "int8"
    assert cfg.with_overrides(kv_cache_precision="int4").kv_precision == "int4"
    # the explicit field supersedes the legacy bool
    assert cfg.with_overrides(kv_cache_precision="fp",
                              kv_cache_int8=True).kv_precision == "fp"
    with pytest.raises(ValueError):
        _ = cfg.with_overrides(kv_cache_precision="int2").kv_precision


# ------------------------------------------------------------------ #
# End-to-end: greedy argmax stability vs fp32 on the smoke arch
# ------------------------------------------------------------------ #
def test_int4_prefill_argmax_stable_vs_fp32():
    """The headline serving claim: swapping the KV cache to the int4 tier
    bounds the logit perturbation at 4-bit quantization scale (measured
    ~0.56 on this seed, vs ~0.04 for int8) and leaves the greedy next
    token unchanged where fp32's top-1/top-2 margin clears that noise."""
    from conftest import make_batch

    cfg_fp = C.smoke_config("mistral-nemo-12b").with_overrides(
        dtype="float32")
    cfg_i4 = cfg_fp.with_overrides(kv_cache_precision="int4")
    params = init_params(jax.random.PRNGKey(0), cfg_fp)
    batch = make_batch(cfg_fp, b=2, s=12)
    fp, _ = prefill(params, batch, cfg_fp)
    i4, _ = prefill(params, batch, cfg_i4)
    fp, i4 = np.asarray(fp[:, -1]), np.asarray(i4[:, -1])
    maxdiff = np.abs(fp - i4).max()
    assert maxdiff < 1.5, maxdiff
    # on this seed the fp32 margins (~0.3) survive the int4 noise; both
    # prompts must keep their greedy token
    srt = np.sort(fp, axis=-1)
    assert (srt[:, -1] - srt[:, -2] > 0.2).all(), "seed lost its margin"
    np.testing.assert_array_equal(fp.argmax(-1), i4.argmax(-1))


def test_int4_engine_dense_matches_paged_streams():
    """Engine-level: the dense int4 engine and the paged int4 engine emit
    identical greedy streams on the ref backend (same quantized writes,
    oracle-equivalent reads)."""
    cfg = C.smoke_config("mistral-nemo-12b").with_overrides(
        dtype="float32", kv_cache_precision="int4")
    params = init_params(jax.random.PRNGKey(0), cfg)
    artifact = ModelArtifact.create("m", "v1", params, cfg)
    prompts = [jax.random.randint(
        jax.random.fold_in(jax.random.PRNGKey(3), i), (1, 10),
        0, cfg.vocab_size) for i in range(3)]

    def run(paged):
        kw = {"paged": True, "block_size": 8} if paged else {}
        engine = ContinuousBatchingEngine(artifact, n_slots=2, max_len=64,
                                          backend="ref", **kw)
        reqs = [engine.submit(p, max_new_tokens=5) for p in prompts]
        engine.run()
        assert all(r.done for r in reqs)
        return [r.out_tokens for r in reqs]

    assert run(paged=False) == run(paged=True)


if __name__ == "__main__":
    pytest.main([__file__, "-q"])
