"""Public entry points for the quantized compute primitives.

These delegate to the backend in scope via the pluggable registry in
``repro.api.backends`` (``ref`` / ``pallas-interpret`` / ``pallas-tpu``);
``repro.models.layers.linear`` calls them for quantized weight leaves, so a
session traced under ``use_backend(...)`` bakes its backend in.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _backend():
    from repro.api.backends import current_backend

    return current_backend()


def _flatten_scale(w_scale) -> jax.Array:
    ws = jnp.asarray(w_scale, jnp.float32)
    return ws.reshape(1, -1) if ws.size > 1 else ws.reshape(1, 1)


def qmatmul_static(x, w_int8, w_scale, act_scale):
    ws = _flatten_scale(w_scale)
    if ws.shape[1] == 1:
        ws = jnp.broadcast_to(ws, (1, w_int8.shape[1]))
    return _backend().qmatmul_static(x, w_int8, ws, act_scale)


def qmatmul_dynamic(x, w_int8, w_scale):
    ws = _flatten_scale(w_scale)
    if ws.shape[1] == 1:
        ws = jnp.broadcast_to(ws, (1, w_int8.shape[1]))
    return _backend().qmatmul_dynamic(x, w_int8, ws)


def quantize_weights(w):
    return _backend().quantize_weights(w)


def qdecode(q, k_i8, k_s, v_i8, v_s, bias):
    """int8-KV decode attention (fused dequant). q [B,Hkv,G,hd]."""
    return _backend().qdecode(q, k_i8, k_s, v_i8, v_s, bias)


def paged_decode(q, k_pool, v_pool, tables, pos):
    """Paged decode attention over block pools (KV-cache v2).

    q [B,Hkv,G,hd]; pools [N,bs,Hkv,hd]; tables [B,M] int32 (-1 =
    unallocated); pos [B] int32. Returns [B,Hkv,G,hd] f32."""
    return _backend().paged_decode(q, k_pool, v_pool, tables, pos)


def paged_qdecode(q, k_pool, k_scale, v_pool, v_scale, tables, pos):
    """int8-KV paged decode attention; scale pools [N,bs,Hkv] f32."""
    return _backend().paged_qdecode(q, k_pool, k_scale, v_pool, v_scale,
                                    tables, pos)


def paged_q4decode(q, k_pool, k_scale, v_pool, v_scale, tables, pos):
    """int4-KV paged decode attention: packed payload pools
    [N,bs,Hkv,hd//2] int8 + per-group scale pools [N,bs,Hkv,hd//g] f32."""
    return _backend().paged_q4decode(q, k_pool, k_scale, v_pool, v_scale,
                                     tables, pos)


def flash_prefill(q, k, v):
    """Fused online-softmax causal prefill attention.

    q [B,S,Hq,hd]; k [B,S,Hkv,hd]; v [B,S,Hkv,dv]. Returns [B,S,Hq,dv]
    f32. Block shapes come from the deterministic autotuner on Pallas
    backends (``kernels.autotune``)."""
    return _backend().flash_prefill(q, k, v)


def flash_qprefill(q, k_i8, k_s, v_i8, v_s):
    """int8-KV fused-dequant flash prefill; scales [B,S,Hkv] f32."""
    return _backend().flash_qprefill(q, k_i8, k_s, v_i8, v_s)


def flash_q4prefill(q, k_i4, k_s, v_i4, v_s):
    """int4-KV fused-dequant flash prefill: packed payloads
    [B,S,Hkv,hd//2] int8 + per-group scales [B,S,Hkv,hd//g] f32."""
    return _backend().flash_q4prefill(q, k_i4, k_s, v_i4, v_s)
