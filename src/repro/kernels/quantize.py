"""Quantization kernels + int4 KV packing helpers.

``quantize_weights``: per-channel symmetric int8 weight quantization as a
Pallas kernel — artifact-build-time (quantize once, deploy many — the
paper's Model Creation pane). Grid over output-channel blocks; each block
stages the full [K, bn] column panel in VMEM, reduces absmax over K, scales
and rounds.

int4 KV tier (grouped quantization, third precision tier): signed 4-bit
codes in [-7, 7] packed two per int8 carrier byte along head_dim, one f16
scale per ``KV_GROUP`` head_dim elements (per-(slot, head, group) rather
than int8's per-(slot, head) f32 scalar — f16 keeps the scale overhead at
2 bytes/group so the int4 tier lands under 0.55x int8 bytes/token; the
scale is an absmax/7 magnitude, far inside f16 range, and its <=2^-11
relative error is noise next to the 4-bit step). ``pack_int4``/
``unpack_int4`` define the wire layout — element ``d`` lives in byte
``d // 2``, even index in the low nibble — and the Pallas kernels read it
in-VMEM through ``unpack_int4_halves``, which yields the same values split
into even and odd elements.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BN = 256

#: head_dim elements per int4 scale group (clamped to head_dim when smaller)
KV_GROUP = 32


def kv_group_size(head_dim: int) -> int:
    """Effective int4 group size: ``KV_GROUP`` clamped to head_dim. head_dim
    is a power of two for every assigned arch, so the clamp always divides."""
    return min(KV_GROUP, head_dim)


def pack_int4(codes):
    """[..., D] int8 codes in [-8, 7] -> [..., D // 2] int8, two codes per
    byte: even index in the low nibble, odd in the high (D must be even)."""
    lo = codes[..., 0::2].astype(jnp.int32) & 0xF
    hi = codes[..., 1::2].astype(jnp.int32) & 0xF
    byte = lo | (hi << 4)                       # 0..255
    return jnp.where(byte >= 128, byte - 256, byte).astype(jnp.int8)


def unpack_int4(packed):
    """[..., D // 2] int8 -> [..., D] int8 codes (sign-extended nibbles)."""
    p = packed.astype(jnp.int32)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    lo = lo - jnp.where(lo >= 8, 16, 0)
    hi = hi - jnp.where(hi >= 8, 16, 0)
    stacked = jnp.stack([lo, hi], axis=-1)      # [..., D//2, 2]
    return stacked.reshape(*packed.shape[:-1],
                           packed.shape[-1] * 2).astype(jnp.int8)


def quantize_kv_int4(t, group_size: int = 0):
    """[..., hd] float -> (packed [..., hd//2] int8, scale [..., hd//g] f16).

    Symmetric per-group absmax (qmax 7, floor 1e-8 like the int8 KV tier);
    ``group_size`` defaults to ``kv_group_size(hd)``. The scale is stored
    f16 but the codes are computed against the ROUNDED f16 scale so that
    dequantize(quantize(x)) reconstructs with the stored scale exactly."""
    hd = t.shape[-1]
    g = group_size or kv_group_size(hd)
    tg = t.astype(jnp.float32).reshape(*t.shape[:-1], hd // g, g)
    absmax = jnp.max(jnp.abs(tg), axis=-1)
    scale = (jnp.maximum(absmax, 1e-8) / 7.0).astype(jnp.float16)
    q = jnp.clip(jnp.round(tg / scale[..., None].astype(jnp.float32)), -7, 7)
    return pack_int4(q.reshape(t.shape).astype(jnp.int8)), scale


def dequantize_kv_int4(t_i4, t_s):
    """(packed [..., hd//2] int8, scale [..., n_groups] f16) -> [..., hd]
    f32. Group size is derived from the shapes (hd / n_groups)."""
    hd = t_i4.shape[-1] * 2
    g = hd // t_s.shape[-1]
    x = unpack_int4(t_i4).astype(jnp.float32)
    xg = x.reshape(*x.shape[:-1], hd // g, g) \
        * t_s[..., None].astype(jnp.float32)
    return xg.reshape(x.shape)


def unpack_int4_halves(packed, scale):
    """Kernel-side dequant: (packed [..., hd//2] int8, scale [..., n_groups]
    f32) -> (even, odd) f32 [..., hd//2], the dequantized elements
    0, 2, 4, ... and 1, 3, 5, ... of each row.

    Every value stays in the lane it was loaded in: Mosaic refuses both the
    nibble interleave of ``unpack_int4`` and the ``[..., groups, g]``
    reshape of ``dequantize_kv_int4``. Callers contract against the even and
    odd halves of q (``split_halves``) and re-interleave what they
    accumulate over values (``interleave_halves``)."""
    p = packed.astype(jnp.int32)
    lo = ((p << 28) >> 28).astype(jnp.float32)    # sign-extended low nibble
    hi = ((p << 24) >> 28).astype(jnp.float32)
    # packed lane j holds elements 2j and 2j+1, both in group j // (g // 2)
    half_group = packed.shape[-1] // scale.shape[-1]
    group = jax.lax.broadcasted_iota(jnp.int32, lo.shape, lo.ndim - 1) \
        // half_group
    s = scale[..., 0:1]
    for i in range(1, scale.shape[-1]):
        s = jnp.where(group == i, scale[..., i:i + 1], s)
    return lo * s, hi * s


def split_halves(x):
    """[..., d] -> ([..., d//2] even elements, [..., d//2] odd elements)."""
    return x[..., 0::2], x[..., 1::2]


def interleave_halves(even, odd):
    """Inverse of ``split_halves``."""
    return jnp.stack([even, odd], axis=-1).reshape(
        *even.shape[:-1], even.shape[-1] * 2)


def _kernel(w_ref, q_ref, scale_ref):
    w = w_ref[...].astype(jnp.float32)                         # [K, bn]
    absmax = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12)
    q_ref[...] = jnp.clip(jnp.round(w * (127.0 / absmax)),
                          -127, 127).astype(jnp.int8)
    scale_ref[...] = absmax / 127.0


@functools.partial(jax.jit, static_argnames=("interpret",))
def quantize_weights(w, *, interpret: bool = False):
    """w [K, N] float -> (w_int8 [K, N], scale [1, N])."""
    k, n = w.shape
    bn = min(BN, n)
    np_ = -(-n // bn) * bn
    w = jnp.pad(w, ((0, 0), (0, np_ - n)), constant_values=1e-12)

    q, scale = pl.pallas_call(
        _kernel,
        grid=(np_ // bn,),
        in_specs=[pl.BlockSpec((k, bn), lambda j: (0, j))],
        out_specs=[
            pl.BlockSpec((k, bn), lambda j: (0, j)),
            pl.BlockSpec((1, bn), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, np_), jnp.int8),
            jax.ShapeDtypeStruct((1, np_), jnp.float32),
        ],
        interpret=interpret,
    )(w)
    return q[:, :n], scale[:, :n]
