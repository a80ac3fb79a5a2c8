"""Paged attention parity: the Pallas gather kernel vs the jnp ref oracle
(fp32 + int8 + int4 KV), the paged model decode vs the dense model decode,
and the MLA paged path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs as C
from repro.api.backends import use_backend
from repro.kernels import paged_attn, ref
from repro.kernels.quantize import quantize_kv_int4
from repro.models import decode_step, decode_step_paged, init_cache, \
    init_params, prefill
from repro.serving.kvcache import PagedKVCache


def _rand_case(seed=0, b=3, hkv=2, g=2, hd=32, n=12, bs=4, m=5):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (b, hkv, g, hd))
    k_pool = jax.random.normal(ks[1], (n, bs, hkv, hd))
    v_pool = jax.random.normal(ks[2], (n, bs, hkv, hd))
    tables = jnp.array([[1, 2, 3, -1, -1],
                        [4, 5, -1, -1, -1],
                        [6, 7, 8, 9, 10]], jnp.int32)
    pos = jnp.array([9, 5, 17], jnp.int32)
    return q, k_pool, v_pool, tables, pos


def _quant(t):
    absmax = jnp.max(jnp.abs(t), axis=-1)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(t / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def test_paged_kernel_matches_ref_fp32():
    q, k_pool, v_pool, tables, pos = _rand_case()
    want = ref.paged_decode_ref(q, k_pool, v_pool, tables, pos)
    got = paged_attn.paged_decode_attention(q, k_pool, v_pool, tables, pos,
                                            interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_kernel_matches_ref_int8():
    q, k_pool, v_pool, tables, pos = _rand_case(seed=1)
    kq, kscale = _quant(k_pool)
    vq, vscale = _quant(v_pool)
    want = ref.paged_qdecode_ref(q, kq, kscale, vq, vscale, tables, pos)
    got = paged_attn.paged_qdecode_attention(q, kq, kscale, vq, vscale,
                                             tables, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_paged_kernel_idle_slot_is_zero_not_nan():
    """An idle slot (table all -1) masks every key: its output must be 0,
    not 0/0. A NaN there reaches the trash block through the slot's K/V
    write, and from there every live sequence with an unallocated table
    entry (``0 * NaN`` in the value contraction)."""
    q, k_pool, v_pool, tables, pos = _rand_case(seed=5)
    tables = tables.at[1].set(-1)
    got = np.asarray(paged_attn.paged_decode_attention(
        q, k_pool, v_pool, tables, pos, interpret=True))
    assert np.all(got[1] == 0.0)
    live = np.array([0, 2])
    want = ref.paged_decode_ref(q[live], k_pool, v_pool, tables[live],
                                pos[live])
    np.testing.assert_allclose(got[live], np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_paged_ref_matches_contiguous_qdecode():
    """Gathering pools through the table must equal the contiguous int8
    oracle on the hand-packed cache (per sequence)."""
    q, k_pool, v_pool, tables, pos = _rand_case(seed=2)
    kq, kscale = _quant(k_pool)
    vq, vscale = _quant(v_pool)
    got = ref.paged_qdecode_ref(q, kq, kscale, vq, vscale, tables, pos)
    b0 = 0
    blocks = [int(x) for x in tables[b0] if x >= 0]
    s = int(pos[b0]) + 1
    pack = lambda p: p[jnp.asarray(blocks)].reshape(-1, *p.shape[2:])[:s][None]
    bias = jnp.zeros((1, s), jnp.float32)
    want = ref.qdecode_ref(q[b0:b0 + 1], pack(kq), pack(kscale),
                           pack(vq), pack(vscale), bias)
    np.testing.assert_allclose(np.asarray(got[b0]), np.asarray(want[0]),
                               rtol=1e-5, atol=1e-5)


def test_paged_kernel_matches_ref_int4():
    """paged_q4decode: the fused-dequant int4 Pallas kernel must match the
    jnp oracle bit-for-float on the same packed pools + f16 group scales."""
    q, k_pool, v_pool, tables, pos = _rand_case(seed=4)
    kq, kscale = quantize_kv_int4(k_pool)
    vq, vscale = quantize_kv_int4(v_pool)
    want = ref.paged_q4decode_ref(q, kq, kscale, vq, vscale, tables, pos)
    got = paged_attn.paged_q4decode_attention(q, kq, kscale, vq, vscale,
                                              tables, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


KV_KINDS = ("fp32", "bf16", "int8", "int4")
_KERNELS = {
    "fp32": (paged_attn.paged_decode_attention, ref.paged_decode_ref),
    "bf16": (paged_attn.paged_decode_attention, ref.paged_decode_ref),
    "int8": (paged_attn.paged_qdecode_attention, ref.paged_qdecode_ref),
    "int4": (paged_attn.paged_q4decode_attention, ref.paged_q4decode_ref),
}


def _ragged_case(seed=7, hkv=2, g=2, hd=32, bs=4, m=6):
    """Five slots whose walks end in different places: ``pos`` at
    ``2*bs - 1`` (last slot of a block) and ``2*bs`` (first of the next),
    one slot at full ``M``, an idle slot (pos 0, table all -1) and a short
    slot. Every live slot's table reaches blocks past its last live one;
    slot 1's last entry is unallocated."""
    b = 5
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, hkv, g, hd))
    n = 1 + b * m                                 # block 0: the trash block
    k_pool = jax.random.normal(ks[1], (n, bs, hkv, hd))
    v_pool = jax.random.normal(ks[2], (n, bs, hkv, hd))
    tables = np.arange(1, n, dtype=np.int32).reshape(b, m)
    tables[1, -1] = -1
    tables[3] = -1
    pos = jnp.array([2 * bs - 1, 2 * bs, m * bs - 1, 0, bs + 1], jnp.int32)
    return q, k_pool, v_pool, jnp.asarray(tables), pos


def _pools(kind, k_pool, v_pool, poison=()):
    """The kernel's pool arguments for KV tier ``kind``; the pool rows in
    ``poison`` hold NaN (fp: the payload; int8/int4: the scales)."""
    rows = jnp.asarray(poison, jnp.int32)
    if kind in ("fp32", "bf16"):
        dt = jnp.bfloat16 if kind == "bf16" else jnp.float32
        return tuple(p.astype(dt).at[rows].set(jnp.nan)
                     for p in (k_pool, v_pool))
    quant = _quant if kind == "int8" else quantize_kv_int4
    (kq, kscale), (vq, vscale) = quant(k_pool), quant(v_pool)
    return (kq, kscale.at[rows].set(jnp.nan), vq,
            vscale.at[rows].set(jnp.nan))


@pytest.mark.parametrize("kind", KV_KINDS)
def test_paged_kernel_ragged_walk_matches_ref(kind):
    """Each slot's walk stops at its own last live block: live slots match
    the oracle wherever ``pos`` falls in a block, and the idle slot is
    exactly 0."""
    q, k_pool, v_pool, tables, pos = _ragged_case()
    kernel, oracle = _KERNELS[kind]
    pools = _pools(kind, k_pool, v_pool)
    got = np.asarray(kernel(q, *pools, tables, pos, interpret=True))
    assert np.all(got[3] == 0.0)
    live = np.array([0, 1, 2, 4])
    want = oracle(q[live], *pools, tables[live], pos[live])
    np.testing.assert_allclose(got[live], np.asarray(want), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("kind", KV_KINDS)
def test_paged_kernel_skips_poisoned_tail(kind):
    """Blocks a slot's table reaches past its last live block may hold
    anything (a stale or recycled block): with NaN written there the output
    is finite and bit-equal to the clean pool's."""
    q, k_pool, v_pool, tables, pos = _ragged_case(seed=8)
    kernel, _ = _KERNELS[kind]
    tabs, last = np.asarray(tables), np.asarray(pos) // k_pool.shape[1]
    poison = [int(t) for row, end in zip(tabs, last) for t in row[end + 1:]
              if t >= 0]
    assert poison
    clean = kernel(q, *_pools(kind, k_pool, v_pool), tables, pos,
                   interpret=True)
    got = np.asarray(kernel(q, *_pools(kind, k_pool, v_pool, poison),
                            tables, pos, interpret=True))
    assert np.all(np.isfinite(got))
    np.testing.assert_array_equal(got, np.asarray(clean))


def test_int4_paged_close_to_fp32_paged():
    """int4-KV accuracy bound: grouped 4-bit quantization perturbs paged
    attention outputs by less than ~20% of the value scale on unit-normal
    data (measured ~14%; int8's bound is 2% — the ~7x step-size gap)."""
    q, k_pool, v_pool, tables, pos = _rand_case(seed=3)
    kq, kscale = quantize_kv_int4(k_pool)
    vq, vscale = quantize_kv_int4(v_pool)
    fp = ref.paged_decode_ref(q, k_pool, v_pool, tables, pos)
    i4 = ref.paged_q4decode_ref(q, kq, kscale, vq, vscale, tables, pos)
    assert float(jnp.max(jnp.abs(fp - i4))) < 0.2 * float(jnp.max(jnp.abs(fp)))


def test_int8_paged_close_to_fp32_paged():
    """int8-KV accuracy bound: quantizing the cache perturbs attention
    outputs by less than ~2% of the value scale on unit-normal data."""
    q, k_pool, v_pool, tables, pos = _rand_case(seed=3)
    kq, kscale = _quant(k_pool)
    vq, vscale = _quant(v_pool)
    fp = ref.paged_decode_ref(q, k_pool, v_pool, tables, pos)
    i8 = ref.paged_qdecode_ref(q, kq, kscale, vq, vscale, tables, pos)
    assert float(jnp.max(jnp.abs(fp - i8))) < 0.02 * float(jnp.max(jnp.abs(fp)))


# ------------------------------------------------------------------ #
# Model-level: paged decode vs dense decode
# ------------------------------------------------------------------ #
def _paged_vs_dense(cfg, backend):
    """Prefill a prompt, then decode N steps through BOTH the dense cache
    and a scattered paged cache — logits must agree step for step."""
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (1, 10),
                                0, cfg.vocab_size)
    bs, n_steps = 4, 6
    _, dense1 = prefill(params, {"tokens": tokens}, cfg, pad_to=32)
    dense = init_cache(cfg, 1, 32)
    dense = jax.tree.map(lambda c, u: u.astype(c.dtype), dense, dense1)

    kv = PagedKVCache(cfg, n_slots=1, n_blocks=10, block_size=bs,
                      max_blocks_per_seq=8)
    kv.scatter_prefill(0, dense1, 10)
    last = jnp.argmax(
        prefill(params, {"tokens": tokens}, cfg, pad_to=32)[0][..., -1, :],
        -1).astype(jnp.int32).reshape(1, 1)
    pos = 10
    tok_d = tok_p = last
    with use_backend(backend):
        for _ in range(n_steps):
            while pos // bs >= len(kv.slot_blocks[0]):
                assert kv.grow(0)
            ld, dense = decode_step(params, dense, tok_d, jnp.int32(pos), cfg)
            lp, kv.pools = decode_step_paged(
                params, kv.pools, tok_p, jnp.full((1,), pos, jnp.int32),
                kv.tables, cfg)
            np.testing.assert_allclose(np.asarray(ld), np.asarray(lp),
                                       rtol=2e-4, atol=2e-4)
            tok_d = jnp.argmax(ld[..., -1, :], -1).astype(jnp.int32).reshape(1, 1)
            tok_p = jnp.argmax(lp[..., -1, :], -1).astype(jnp.int32).reshape(1, 1)
            assert jnp.array_equal(tok_d, tok_p)
            pos += 1


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_gqa_paged_decode_matches_dense(backend):
    cfg = C.smoke_config("mistral-nemo-12b").with_overrides(dtype="float32")
    _paged_vs_dense(cfg, backend)


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_gqa_paged_decode_matches_dense_int8(backend):
    cfg = C.smoke_config("mistral-nemo-12b").with_overrides(
        dtype="float32", kv_cache_int8=True)
    _paged_vs_dense(cfg, backend)


@pytest.mark.parametrize("backend", ["ref", "pallas-interpret"])
def test_gqa_paged_decode_matches_dense_int4(backend):
    """paged_q4decode through the block table == the dense int4 decode on
    the contiguous cache, step for step (both sides quantize identically,
    so the delta is pure gather/kernel numerics)."""
    cfg = C.smoke_config("mistral-nemo-12b").with_overrides(
        dtype="float32", kv_cache_precision="int4")
    _paged_vs_dense(cfg, backend)


def test_mla_paged_decode_matches_dense():
    cfg = C.smoke_config("deepseek-v2-236b").with_overrides(dtype="float32")
    _paged_vs_dense(cfg, "ref")


def test_mla_paged_decode_matches_dense_absorbed():
    cfg = C.smoke_config("deepseek-v2-236b").with_overrides(
        dtype="float32", opt_mla_absorb=True)
    _paged_vs_dense(cfg, "ref")
