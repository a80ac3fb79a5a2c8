"""Online-softmax state shared by the attention kernels.

Flash prefill, paged decode and dense int8 decode all stream K/V tiles
through the innermost (sequential) grid dimension and keep, per query row,
a running max ``m``, normalizer ``l`` and weighted accumulator in VMEM
scratch, rescaled by ``exp(m_prev - m_new)`` as each tile arrives.

``idx`` selects the state slice a step updates: a head index for the
decode kernels (which hold every kv head of a block in one grid step, so
their scratch is ``[Hkv, rows, ...]``), ``...`` for flash prefill (one
head per grid step).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0e38
RUN_INIT = -1.0e30          # running-max seed (fits f32 after subtraction)


def dot_nt(a, b):
    """``a [r, d] @ b [n, d].T`` in f32 — the scores contraction."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def init_state(acc_refs, m_ref, l_ref):
    for acc_ref in acc_refs:
        acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, RUN_INIT)
    l_ref[...] = jnp.zeros_like(l_ref)


def step(idx, scores, vals, acc_refs, m_ref, l_ref):
    """One online-softmax step: ``scores [rows, n]`` (masked) and one value
    panel ``[n, w]`` per accumulator (the int4 kernels keep even and odd
    head_dim lanes in separate accumulators)."""
    m_prev = m_ref[idx]                                    # [rows, 1]
    m_new = jnp.maximum(m_prev, jnp.max(scores, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                        # [rows, 1]
    p = jnp.exp(scores - m_new)                            # [rows, n]
    l_ref[idx] = l_ref[idx] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    for acc_ref, v in zip(acc_refs, vals):
        acc_ref[idx] = acc_ref[idx] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    m_ref[idx] = m_new


def normalize(acc, l):
    """``acc / l``; rows whose every key was masked (an idle decode slot)
    come out 0 instead of 0/0. A NaN there would reach the pool's trash
    block through the slot's K/V write and poison, via ``0 * NaN`` in the
    value contraction, every sequence whose table reaches that block."""
    return acc / jnp.where(l > 0, l, 1.0)
