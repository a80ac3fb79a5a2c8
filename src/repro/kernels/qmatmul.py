"""Static w8a8 int8 matmul Pallas kernel (TPU target, MXU-tiled).

Hardware adaptation (DESIGN.md §2): on the v5e MXU, int8 matmul runs at 2x
bf16 peak and weight HBM traffic drops 4x vs fp32 — the TPU-native version of
the paper's Pi-4 int8 speedup. The activation scale is *static* (calibrated),
so quantize->dot->dequantize fuses into one VMEM pass, grid (M/bm, N/bn, K/bk)
with an int32 VMEM accumulator across the K dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BM, BN, BK = 128, 128, 512


def _kernel(x_ref, w_ref, wscale_ref, ascale_ref, o_ref, acc_ref, *, nk: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a_scale = ascale_ref[0, 0]
    x = x_ref[...].astype(jnp.float32)
    xq = jnp.clip(jnp.round(x * (1.0 / a_scale)), -127, 127).astype(jnp.int8)
    acc_ref[...] += jax.lax.dot_general(
        xq, w_ref[...],
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
        # an int8 dot is exact; an f32 contract precision in scope (e.g.
        # default_matmul_precision("highest")) makes Mosaic refuse it
        precision=jax.lax.Precision.DEFAULT,
    )

    @pl.when(pl.program_id(2) == nk - 1)
    def _finalize():
        o_ref[...] = acc_ref[...].astype(jnp.float32) * (
            a_scale * wscale_ref[...].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def qmatmul_static(x, w_int8, w_scale, act_scale, *, interpret: bool = False):
    """x [M, K] float; w_int8 [K, N] int8; w_scale [1, N]; act_scale scalar."""
    m, k = x.shape
    _, n = w_int8.shape
    bm, bn, bk = min(BM, m), min(BN, n), min(BK, k)
    # pad to block multiples (zero rows/cols contribute zero to the dot)
    mp, np_, kp = -(-m // bm) * bm, -(-n // bn) * bn, -(-k // bk) * bk
    x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    w_int8 = jnp.pad(w_int8, ((0, kp - k), (0, np_ - n)))
    w_scale = jnp.pad(w_scale, ((0, 0), (0, np_ - n)))
    nk = kp // bk
    ascale = jnp.reshape(act_scale.astype(jnp.float32), (1, 1))

    out = pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
    )(x, w_int8, w_scale, ascale)
    return out[:m, :n]
