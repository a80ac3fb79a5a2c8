"""int8-KV decode attention Pallas kernel (beyond-paper §Perf optimization).

The paper quantizes *weights*; decode_32k is KV-cache-memory-bound, so we
extend the same signed-int8 scheme to the KV cache. The kernel fuses
dequantization into the attention dot, so HBM traffic for the cache is
1 byte/elem (vs 2 for bf16) and the f32 dequantized cache never exists in
HBM — only per-(slot, head) scales (S*H floats) are added.

Layout: grid ``(B, S / tile)``. Each step takes one ``tile``-slot panel
with all ``Hkv`` heads (a one-head block's second-minor dim of 1 is refused
by the TPU compiler, as in ``paged_attn``) and folds it into a per-head
online softmax (``kernels.online_softmax``), so VMEM holds one tile, never
the whole ``[S, hd]`` panel.

    q        [B, Hkv, G, hd]   (G = query heads per kv head)
    k_i8/v_i8[B, S, Hkv, hd]   int8
    k_s/v_s  [B, S, Hkv]       f32 per-slot-per-head scales
    bias     [B, S]            additive mask (0 or -inf), ring-aware
    out      [B, Hkv, G, hd]   f32
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import online_softmax as osm

#: cache slots per grid step (a multiple of 128: the bias block is
#: lane-major); shorter caches run as one whole-S step. The kernel unrolls
#: its head loop, so compile time grows with tile x Hkv: at Hkv 32 a
#: 512-slot tile took ~50 s to compile for v5e, 128 slots ~4 s
TILE = 128


def _kernel(q_ref, k_ref, ks_ref, v_ref, vs_ref, bias_ref, o_ref,
            acc_ref, m_ref, l_ref):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        osm.init_state([acc_ref], m_ref, l_ref)

    bias = bias_ref[0]                                    # [1, tile]
    hd = q_ref.shape[-1]
    for h in range(k_ref.shape[2]):
        scores = osm.dot_nt(q_ref[0, h].astype(jnp.float32),
                            k_ref[0, :, h].astype(jnp.float32))
        scores = scores * ks_ref[0, :, h][None, :] \
            / jnp.sqrt(jnp.float32(hd)) + bias
        # fold v scales into v — same products as scaling the probs
        v = v_ref[0, :, h].astype(jnp.float32) * vs_ref[0, :, h:h + 1]
        osm.step(h, scores, [v], [acc_ref], m_ref, l_ref)

    @pl.when(si == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = osm.normalize(acc_ref[...], l_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def qdecode_attention(q, k_i8, k_s, v_i8, v_s, bias, *, interpret: bool = False):
    """q [B,Hkv,G,hd]; k_i8/v_i8 [B,S,Hkv,hd]; k_s/v_s [B,S,Hkv]; bias [B,S]."""
    b, hkv, g, hd = q.shape
    s = k_i8.shape[1]
    tile = min(TILE, s)
    sp = -(-s // tile) * tile
    if sp != s:
        # padded slots: zero payloads and scales, masked by the bias
        pad = [(0, 0), (0, sp - s)]
        k_i8, v_i8 = (jnp.pad(t, pad + [(0, 0), (0, 0)]) for t in (k_i8, v_i8))
        k_s, v_s = (jnp.pad(t, pad + [(0, 0)]) for t in (k_s, v_s))
        bias = jnp.pad(bias, pad, constant_values=osm.NEG_INF)
    kv_spec = pl.BlockSpec((1, tile, hkv, hd), lambda i, j: (i, j, 0, 0))
    scale_spec = pl.BlockSpec((1, tile, hkv), lambda i, j: (i, j, 0))
    row_spec = pl.BlockSpec((1, hkv, g, hd), lambda i, j: (i, 0, 0, 0))
    return pl.pallas_call(
        _kernel,
        grid=(b, sp // tile),
        in_specs=[row_spec, kv_spec, scale_spec, kv_spec, scale_spec,
                  pl.BlockSpec((1, 1, tile), lambda i, j: (i, 0, j))],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), jnp.float32),
        scratch_shapes=[pltpu.VMEM((hkv, g, hd), jnp.float32),
                        pltpu.VMEM((hkv, g, 1), jnp.float32),
                        pltpu.VMEM((hkv, g, 1), jnp.float32)],
        interpret=interpret,
    )(q, k_i8, k_s, v_i8, v_s, bias.reshape(b, 1, sp))
