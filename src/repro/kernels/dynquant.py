"""Dynamic w8a8 int8 matmul Pallas kernel (fused activation quantization).

The paper's *dynamic* mode needs a data-dependent per-row activation scale.
A naive implementation does two HBM passes (absmax, then matmul); here the
row block [bm, K] is staged once into VMEM, absmax/quantize/dot all happen
in-registers — the fusion that narrows the static-vs-dynamic gap on TPU
(DESIGN.md §2). Grid (M/bm, N/bn) with the full K per block
(K*bm*4B <= ~9 MB for the largest assigned d_ff, well inside VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BM, BN = 128, 128


def _kernel(x_ref, w_ref, wscale_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)                     # [bm, K]
    absmax = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True), 1e-12)
    inv = 127.0 / absmax                                   # reciprocal form:
    a_scale = absmax / 127.0                               # matches ref.py
    xq = jnp.clip(jnp.round(x * inv), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(
        xq, w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
        # an int8 dot is exact; an f32 contract precision in scope (e.g.
        # default_matmul_precision("highest")) makes Mosaic refuse it
        precision=jax.lax.Precision.DEFAULT,
    )
    o_ref[...] = acc.astype(jnp.float32) * a_scale * wscale_ref[...].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def qmatmul_dynamic(x, w_int8, w_scale, *, interpret: bool = False):
    """x [M, K] float; w_int8 [K, N] int8; w_scale [1, N] f32."""
    m, k = x.shape
    _, n = w_int8.shape
    bm, bn = min(BM, m), min(BN, n)
    mp, np_ = -(-m // bm) * bm, -(-n // bn) * bn
    x = jnp.pad(x, ((0, mp - m), (0, 0)))
    w_int8 = jnp.pad(w_int8, ((0, 0), (0, np_ - n)))
    w_scale = jnp.pad(w_scale, ((0, 0), (0, np_ - n)))

    out = pl.pallas_call(
        _kernel,
        grid=(mp // bm, np_ // bn),
        in_specs=[
            pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
            pl.BlockSpec((k, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        interpret=interpret,
    )(x, w_int8, w_scale)
    return out[:m, :n]
