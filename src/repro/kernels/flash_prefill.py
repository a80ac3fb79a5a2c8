"""Fused flash-prefill Pallas kernels (online-softmax over tile pairs).

Prefill attention computed as query tiles x KV tiles with the classic
flash-attention recurrence: per query row a running max ``m``, normalizer
``l`` and weighted accumulator, rescaled by ``exp(m_prev - m_new)`` as KV
tiles stream through the innermost (sequential) grid dimension. The dense
``[S, S]`` score matrix never materializes, and causal tile pairs strictly
above the diagonal are skipped entirely — roughly half the flops of the
naive path at long prompts.

GQA is handled by flattening query groups into the row dimension on the
host: ``q [B, S, Hq, hd]`` becomes ``[B, Hkv, S*G, hd]`` with row
``r = s * G + g`` so each query tile covers ``block_q`` *positions*
(``block_q * G`` rows) and shares its KV tile stream. MLA lands here with
``G = 1`` and a value head dim that may differ from ``hd``.

Three variants share the machinery (mirroring ``paged_attn``):

    flash_prefill_attention    fp32/bf16 K/V
    flash_qprefill_attention   int8 K/V + per-(pos, head) f32 scales,
                               dequant fused into the dots
    flash_q4prefill_attention  int4 K/V packed two codes per byte along
                               head_dim + per-(pos, head, group) f32
                               scales; nibbles unpack + dequantize in VMEM

Shapes (model layout in, model layout out):
    q            [B, S, Hq, hd]
    k            [B, S, Hkv, hd]     (int8 variant: int8 + scale [B, S, Hkv];
                                      int4: [B, S, Hkv, hd // 2] packed +
                                      scale [B, S, Hkv, hd // group])
    v            [B, S, Hkv, dv]
    out          [B, S, Hq, dv]      f32

Scales travel as ``[B, Hkv, S, 1]`` columns (int8) and ``[B, Hkv, S,
n_groups]`` f32 (int4), so every block's last two dims are whole or
8-aligned, as the TPU compiler requires; the int4 kernel contracts the
even/odd head_dim halves of q (``quantize.unpack_int4_halves``).

Interpret-mode note: the Pallas interpreter executes grid steps in Python,
so long prompts would run at interpreter speed. With ``interpret=True`` and
more than ``INTERPRET_MAX_SEQ`` tokens the call routes to the XLA-compiled
tiled oracle in ``kernels.ref`` (identical tiling and accumulation order,
same causal tile skip). Compiled for the chip the kernel always runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import online_softmax as osm
from repro.kernels.quantize import (interleave_halves, split_halves,
                                    unpack_int4_halves)

# interpret mode runs grid steps in Python — beyond this length it routes
# to the XLA tiled oracle
INTERPRET_MAX_SEQ = 256

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _positions(qi, ki, g, bq, bk, rows):
    """Query/key positions for tile pair (qi, ki): rows are group-flattened
    (``r = pos * g + group``), keys are plain positions."""
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // g
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (1, bk), 1)
    return q_pos, k_pos


def _fp_tile(qs, kv_refs):
    """(scores [rows, bk], value panels) of one tile pair for fp K/V."""
    (q,), (k_ref, v_ref) = qs, kv_refs
    scores = osm.dot_nt(q, k_ref[0, 0].astype(jnp.float32))
    return scores, [v_ref[0, 0].astype(jnp.float32)]


def _q_tile(qs, kv_refs):
    # int8 payloads dequantize per row against [bk, 1] scale columns —
    # the ``payload * scale`` products of the oracle
    (q,), (k_ref, ks_ref, v_ref, vs_ref) = qs, kv_refs
    k = k_ref[0, 0].astype(jnp.float32) * ks_ref[0, 0]
    v = v_ref[0, 0].astype(jnp.float32) * vs_ref[0, 0]
    return osm.dot_nt(q, k), [v]


def _q4_tile(qs, kv_refs):
    # unpack nibbles + per-group dequant in VMEM; only the packed bytes and
    # the [bk, n_groups] scales crossed HBM
    (q_even, q_odd), (k_ref, ks_ref, v_ref, vs_ref) = qs, kv_refs
    k_even, k_odd = unpack_int4_halves(k_ref[0, 0], ks_ref[0, 0])
    v_even, v_odd = unpack_int4_halves(v_ref[0, 0], vs_ref[0, 0])
    scores = osm.dot_nt(q_even, k_even) + osm.dot_nt(q_odd, k_odd)
    return scores, [v_even, v_odd]


def _kernel(tile_fn, n_q, hd, *refs, g, bq, bk, s, nk):
    """refs: n_q query parts, the K/V (+ scale) tiles, n_q outputs, n_q
    accumulators, running max, normalizer."""
    n_kv = len(refs) - 3 * n_q - 2
    q_refs, kv_refs = refs[:n_q], refs[n_q:n_q + n_kv]
    o_refs = refs[n_q + n_kv:2 * n_q + n_kv]
    acc_refs, (m_ref, l_ref) = refs[2 * n_q + n_kv:-2], refs[-2:]
    qi, ki = pl.program_id(2), pl.program_id(3)
    rows = q_refs[0].shape[2]

    @pl.when(ki == 0)
    def _init():
        osm.init_state(acc_refs, m_ref, l_ref)

    q_last = qi * bq + bq - 1          # last query position in this tile
    last = jnp.minimum(nk - 1, q_last // bk)

    @pl.when(ki * bk <= q_last)        # causal: skip tiles above diagonal
    def _compute():
        scores, vals = tile_fn([r[0, 0].astype(jnp.float32) for r in q_refs],
                               kv_refs)
        scores = scores / jnp.sqrt(jnp.float32(hd))
        q_pos, k_pos = _positions(qi, ki, g, bq, bk, rows)
        scores = jnp.where((k_pos <= q_pos) & (k_pos < s), scores,
                           osm.NEG_INF)
        osm.step(..., scores, vals, acc_refs, m_ref, l_ref)

        @pl.when(ki == last)
        def _finish():
            for o_ref, acc_ref in zip(o_refs, acc_refs):
                o_ref[0, 0] = osm.normalize(acc_ref[...],
                                                l_ref[...])


def _pad_seq(x, target):
    s = x.shape[1]
    if s == target:
        return x
    pad = [(0, 0), (0, target - s)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)


def _split_heads(q, k_like, hkv):
    """Model layout -> kernel layout: q rows group-flattened per kv head;
    K/V ``[B, S, Hkv, w]`` -> ``[B, Hkv, S, w]``, per-position scales
    ``[B, S, Hkv]`` -> ``[B, Hkv, S, 1]`` columns."""
    b, sq, hq, hd = q.shape
    g = hq // hkv
    qr = q.reshape(b, sq, hkv, g, hd).transpose(0, 2, 1, 3, 4)
    qr = qr.reshape(b, hkv, sq * g, hd)
    return qr, [t.transpose(0, 2, 1, 3) if t.ndim == 4
                else t.transpose(0, 2, 1)[..., None] for t in k_like]


def _merge_heads(out, b, sq, hkv, g, dv, s):
    out = out.reshape(b, hkv, sq, g, dv).transpose(0, 2, 1, 3, 4)
    return out.reshape(b, sq, hkv * g, dv)[:, :s]


def _clip_blocks(s, block_q, block_k):
    bq = max(1, min(block_q or DEFAULT_BLOCK_Q, s))
    bk = max(1, min(block_k or DEFAULT_BLOCK_K, s))
    return bq, bk


def _flash(tile_fn, q, kv, *, dv, block_q, block_k, interpret):
    """Pad to whole tiles, lay out per kv head, run ``tile_fn``'s kernel and
    return the model layout. ``kv`` holds ``[B, S, Hkv, ...]`` payloads and
    scales; int4 (``tile_fn is _q4_tile``) splits q into head_dim halves."""
    b, s, hq, hd = q.shape
    hkv = kv[0].shape[2]
    g = hq // hkv
    bq, bk = _clip_blocks(s, block_q, block_k)
    nq, nk = -(-s // bq), -(-s // bk)
    qr, kvr = _split_heads(_pad_seq(q, nq * bq),
                           [_pad_seq(t, nk * bk) for t in kv], hkv)
    qs = list(split_halves(qr)) if tile_fn is _q4_tile else [qr]
    rows, w = bq * g, dv // len(qs)
    row_spec = pl.BlockSpec((1, 1, rows, w),
                            lambda b_, h, qi, ki: (b_, h, qi, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        grid=(b, hkv, nq, nk),
        in_specs=[pl.BlockSpec((1, 1, rows, t.shape[-1]),
                               lambda b_, h, qi, ki: (b_, h, qi, 0))
                  for t in qs]
        + [pl.BlockSpec((1, 1, bk, t.shape[-1]),
                        lambda b_, h, qi, ki: (b_, h, ki, 0)) for t in kvr],
        out_specs=[row_spec] * len(qs),
        scratch_shapes=[pltpu.VMEM((rows, w), jnp.float32)] * len(qs)
        + [pltpu.VMEM((rows, 1), jnp.float32)] * 2,
    )
    kernel = functools.partial(_kernel, tile_fn, len(qs), hd,
                               g=g, bq=bq, bk=bk, s=s, nk=nk)
    outs = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, nq * rows, w),
                                        jnp.float32)] * len(qs),
        interpret=interpret,
    )(*qs, *kvr)
    out = interleave_halves(*outs) if len(outs) == 2 else outs[0]
    return _merge_heads(out, b, nq * bq, hkv, g, dv, s)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def flash_prefill_attention(q, k, v, *, block_q=None, block_k=None,
                            interpret: bool = False):
    """fp32/bf16 fused flash prefill — see module docstring for shapes."""
    if interpret and q.shape[1] > INTERPRET_MAX_SEQ:
        from repro.kernels import ref as _ref
        return _ref.flash_prefill_ref(q, k, v)
    return _flash(_fp_tile, q, [k, v], dv=v.shape[3], block_q=block_q,
                  block_k=block_k, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def flash_qprefill_attention(q, k_i8, k_scale, v_i8, v_scale, *,
                             block_q=None, block_k=None,
                             interpret: bool = False):
    """int8-KV fused-dequant flash prefill."""
    if interpret and q.shape[1] > INTERPRET_MAX_SEQ:
        from repro.kernels import ref as _ref
        return _ref.flash_qprefill_ref(q, k_i8, k_scale, v_i8, v_scale)
    return _flash(_q_tile, q, [k_i8, k_scale, v_i8, v_scale],
                  dv=v_i8.shape[3], block_q=block_q, block_k=block_k,
                  interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("block_q", "block_k", "interpret"))
def flash_q4prefill_attention(q, k_i4, k_scale, v_i4, v_scale, *,
                              block_q=None, block_k=None,
                              interpret: bool = False):
    """int4-KV fused-dequant flash prefill: packed payloads
    [B, S, Hkv, hd // 2] + per-group scales [B, S, Hkv, hd // group] (f16
    scales enter as f32: Mosaic has no f16 vector loads)."""
    if interpret and q.shape[1] > INTERPRET_MAX_SEQ:
        from repro.kernels import ref as _ref
        return _ref.flash_q4prefill_ref(q, k_i4, k_scale, v_i4, v_scale)
    return _flash(_q4_tile, q,
                  [k_i4, k_scale.astype(jnp.float32),
                   v_i4, v_scale.astype(jnp.float32)],
                  dv=v_i4.shape[3] * 2, block_q=block_q, block_k=block_k,
                  interpret=interpret)
