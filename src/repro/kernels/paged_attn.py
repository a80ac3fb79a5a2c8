"""Paged gather-attention Pallas kernels (KV-cache v2 tentpole).

Decode attention that reads K/V straight out of the shared block pool via
per-sequence block tables — the dense ``[B, S]`` cache view never
materializes in HBM. The block table (and per-sequence positions) ride the
TPU scalar-prefetch path: the grid is ``(B, M)`` and the *index map* of the
K/V pool specs picks physical block ``tables[b, min(m, last[b])]`` for grid
step ``m`` — paging happens in the prefetch unit. Each sequence's walk stops
at its last live block ``last = pos // bs``: past it the block index
repeats, so the pipeline issues no DMA, and the body computes nothing.
Only the grid steps themselves remain, ``M`` per sequence. ``last`` is
prefetched beside ``pos`` rather than divided out per grid step: the
scalar division cost about 0.05 us of every step, skipped or live (v5e).

Each grid step takes one block with all ``Hkv`` heads, ``(1, bs, Hkv,
hd)``: on the pool's ``[N, bs, Hkv, hd]`` layout a one-head block would
have a second-minor dim of 1, which the TPU compiler refuses (a block's
last two dims must be (8, 128)-aligned or whole). The heads are a static
loop inside the kernel, each with its own online-softmax state
(``kernels.online_softmax``) accumulated across the ``M`` (innermost,
sequential) grid dimension.

Three variants share the machinery:

    paged_decode_attention    fp32/bf16 pools
    paged_qdecode_attention   int8 pools + per-(block, slot, head) f32
                              scales, dequant fused into the dots (HBM
                              traffic: 1 byte/elem, same scheme as qdecode)
    paged_q4decode_attention  int4 pools (two codes per byte, packed along
                              head_dim) + per-(block, slot, head, group)
                              scales; nibbles unpack and dequantize in
                              VMEM (HBM traffic: 0.5 byte/elem). q enters
                              as even/odd head_dim halves and the output
                              leaves as halves, re-interleaved outside
                              (``quantize.unpack_int4_halves``)

Shapes:
    q           [B, Hkv, G, hd]    (G = query heads per kv head)
    k/v pool    [N, bs, Hkv, hd]   (bs = tokens per block;
                                    int4: [N, bs, Hkv, hd // 2] packed)
    k/v scales  [N, bs, Hkv]       (int8 variant;
                                    int4: [N, bs, Hkv, hd // group])
    tables      [B, M] int32       (-1 = unallocated, clamped + masked)
    pos         [B]   int32        (current write position, inclusive)
    out         [B, Hkv, G, hd]    f32
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


def _fp_head(h, q_refs, kv_refs):
    """(scores [G, bs], value panels) of kv head ``h`` for fp pools."""
    (q_ref,), (k_ref, v_ref) = q_refs, kv_refs
    scores = osm.dot_nt(q_ref[0, h].astype(jnp.float32),
                        k_ref[0, :, h].astype(jnp.float32))
    return scores, [v_ref[0, :, h].astype(jnp.float32)]


def _q_head(h, q_refs, kv_refs):
    (q_ref,), (k_ref, ks_ref, v_ref, vs_ref) = q_refs, kv_refs
    scores = osm.dot_nt(q_ref[0, h].astype(jnp.float32),
                        k_ref[0, :, h].astype(jnp.float32))   # int8 -> f32
    # fold v scales into v (per-slot column) — same products as scaling
    # the probabilities, so the accumulator is shared with fp
    v = v_ref[0, :, h].astype(jnp.float32) * vs_ref[0, :, h:h + 1]
    return scores * ks_ref[0, :, h][None, :], [v]


def _q4_head(h, q_refs, kv_refs):
    (qe_ref, qo_ref), (k_ref, ks_ref, v_ref, vs_ref) = q_refs, kv_refs
    k_even, k_odd = unpack_int4_halves(k_ref[0, :, h], ks_ref[0, :, h])
    v_even, v_odd = unpack_int4_halves(v_ref[0, :, h], vs_ref[0, :, h])
    scores = (osm.dot_nt(qe_ref[0, h].astype(jnp.float32), k_even)
              + osm.dot_nt(qo_ref[0, h].astype(jnp.float32), k_odd))
    return scores, [v_even, v_odd]


def _kernel(head_fn, n_q, hd, tables_ref, pos_ref, last_ref, *refs):
    """refs: n_q query parts, the K/V (+ scale) blocks, n_q outputs, n_q
    accumulators, running max, normalizer."""
    n_kv = len(refs) - 3 * n_q - 2
    q_refs, kv_refs = refs[:n_q], refs[n_q:n_q + n_kv]
    o_refs = refs[n_q + n_kv:2 * n_q + n_kv]
    acc_refs, (m_ref, l_ref) = refs[2 * n_q + n_kv:-2], refs[-2:]
    bi, mi = pl.program_id(0), pl.program_id(1)
    k_ref = kv_refs[0]
    bs, hkv = k_ref.shape[1], k_ref.shape[2]

    @pl.when(mi == 0)
    def _init():
        osm.init_state(acc_refs, m_ref, l_ref)

    # blocks past the last live one are skipped, not masked: a masked block
    # adds nothing to the online softmax, but 0 * NaN from a stale block
    # would reach the value contraction
    @pl.when(mi <= last_ref[bi])
    def _live():
        slots = mi * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
        ok = (slots <= pos_ref[bi]) & (tables_ref[bi, mi] >= 0)
        for h in range(hkv):
            scores, vals = head_fn(h, q_refs, kv_refs)
            scores = jnp.where(ok, scores / jnp.sqrt(jnp.float32(hd)),
                               osm.NEG_INF)
            osm.step(h, scores, vals, acc_refs, m_ref, l_ref)

    @pl.when(mi == pl.num_programs(1) - 1)
    def _finish():
        for o_ref, acc_ref in zip(o_refs, acc_refs):
            o_ref[0] = osm.normalize(acc_ref[...], l_ref[...])


def _pool_spec(block_shape):
    # index map args: (grid indices..., scalar-prefetch refs) — block m of
    # sequence b lives at physical pool row tables[b, m] (clamped: -1 reads
    # the reserved trash block, masked out in the kernel). Past the last
    # live block the index repeats, so the pipeline fetches nothing new.
    zeros = (0,) * (len(block_shape) - 1)

    def index(b, m, tabs, pos, last):
        return (jnp.maximum(tabs[b, jnp.minimum(m, last[b])], 0), *zeros)

    return pl.BlockSpec(block_shape, index)


def _call(head_fn, qs, pools, tables, pos, hd, interpret):
    b, hkv, g, w = qs[0].shape
    m = tables.shape[1]
    pos = pos.astype(jnp.int32)
    row_spec = pl.BlockSpec((1, hkv, g, w),
                            lambda b_, m_, *prefetch: (b_, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, m),
        in_specs=[row_spec] * len(qs)
        + [_pool_spec((1,) + p.shape[1:]) for p in pools],
        out_specs=[row_spec] * len(qs),
        scratch_shapes=[pltpu.VMEM((hkv, g, w), jnp.float32)] * len(qs)
        + [pltpu.VMEM((hkv, g, 1), jnp.float32)] * 2,
    )
    return pl.pallas_call(
        functools.partial(_kernel, head_fn, len(qs), hd),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, hkv, g, w), jnp.float32)]
        * len(qs),
        interpret=interpret,
    )(tables.astype(jnp.int32), pos, pos // pools[0].shape[1], *qs,
      *pools)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(q, k_pool, v_pool, tables, pos, *,
                           interpret: bool = False):
    """fp32/bf16 paged decode attention — see module docstring for shapes."""
    (out,) = _call(_fp_head, [q], [k_pool, v_pool], tables, pos,
                   q.shape[-1], interpret)
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_qdecode_attention(q, k_pool, k_scale, v_pool, v_scale, tables, pos,
                            *, interpret: bool = False):
    """int8-KV paged decode attention with fused dequant."""
    (out,) = _call(_q_head, [q], [k_pool, k_scale, v_pool, v_scale],
                   tables, pos, q.shape[-1], interpret)
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_q4decode_attention(q, k_pool, k_scale, v_pool, v_scale, tables,
                             pos, *, interpret: bool = False):
    """int4-KV paged decode attention: packed payload pools + per-group
    scale pools, nibble unpack + grouped dequant fused into the kernel.
    The f16 scale pools enter as f32 (Mosaic has no f16 vector loads)."""
    even, odd = _call(_q4_head, list(split_halves(q)),
                      [k_pool, k_scale.astype(jnp.float32),
                       v_pool, v_scale.astype(jnp.float32)],
                      tables, pos, q.shape[-1], interpret)
    return interleave_halves(even, odd)
