"""The plain float32 reference of the served model, independent of
``repro.models``: what every block shares. Each configuration's block
(``blocks/<block>.py``) gives the forward to the final hidden states,
written from its own equations over its own stacks of layers; this module
gives it the matmul (``mm``, at ``highest`` precision, with the control's
lower precision put in), the norm, and ``over_layers``, which takes the
hidden states through one stack a layer at a time. ``gaps`` runs a block
over each checked sequence and applies the final RMSNorm and the untied
head. No cache, no kernels, no batching: each sequence runs whole. Weights
split over several chips on their layer axis are read one layer at a time
on the first chip, where that layer is upcast.

``quant`` puts a lower precision in the matmuls of the linear layers and
the head (weights per output column, activations per token): ``"fp8"``
(e4m3) or ``"int8"``. That is the control: what the comparison must fail.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

FP8_MAX = 448.0


def _fake_quant(x, axis, quant):
    amax = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30)
    if quant == "int8":
        s = amax / 127.0
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    if quant == "fp8":
        s = amax / FP8_MAX
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control precision {quant!r}")


def mm(x, w, quant):
    """x @ w in float32 at ``highest`` precision, both operands taken to
    the lower precision ``quant`` first where it is given."""
    w = w.astype(jnp.float32)
    if quant:
        x = _fake_quant(x, -1, quant)
        w = _fake_quant(w, 0, quant)
    return jnp.einsum("...k,kn->...n", x, w,
                      precision=jax.lax.Precision.HIGHEST)


def norm(x, w, eps):
    """RMSNorm scaled by ``1 + w``."""
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * (1.0 + w.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, final_norm, unembed, rows, eps, quant):
    """Logits [P, V] at positions ``rows`` of x [S, d]."""
    h = norm(x[rows], final_norm, eps)
    return mm(h, unembed, quant)


@jax.jit
def _gaps(ref_logits, other_logits, served, valid):
    """For each read position: max(ref) - ref[served token], and
    max(ref) - ref[the other precision's first choice]."""
    top = jnp.max(ref_logits, axis=-1)
    at_served = jnp.take_along_axis(ref_logits, served[:, None], -1)[:, 0]
    pick = jnp.argmax(other_logits, axis=-1)
    at_pick = jnp.take_along_axis(ref_logits, pick[:, None], -1)[:, 0]
    return (jnp.where(valid, top - at_served, 0.0),
            jnp.where(valid, top - at_pick, 0.0))


def _spread(stack) -> bool:
    """Whether a stack's weights lie on more than one device."""
    return len(jax.tree.leaves(stack)[0].sharding.device_set) > 1


def _layer_to(layers, i, device):
    """Layer ``i`` of stacked leaves split on their layer axis, as a stack
    of one on ``device``: sliced from the shard that holds it, and only
    that layer moved."""
    def one(a):
        for sh in a.addressable_shards:
            lo, hi, _ = sh.index[0].indices(a.shape[0])
            if lo <= i < hi:
                return jax.device_put(sh.data[i - lo:i - lo + 1], device)
        raise ValueError(f"no shard holds layer {i}")
    return jax.tree.map(one, layers)


def over_layers(x, stack, layer):
    """x through every layer of ``stack`` (leaves stacked on their first
    axis) in turn: ``layer(x, stack, i)`` applies layer ``i`` of the stack
    it is given. A stack spread over several devices is read one layer at
    a time on the first of them, as a stack of one."""
    n_layers = jax.tree.leaves(stack)[0].shape[0]
    if not _spread(stack):
        for i in range(n_layers):
            x = layer(x, stack, jnp.int32(i))
        return x
    dev = jax.devices()[0]
    x = jax.device_put(x, dev)
    for i in range(n_layers):
        x = layer(x, _layer_to(stack, i, dev), jnp.int32(0))
    return x


def gaps(params, block, model: dict, seqs, pad_to: int, read_len: int,
         controls=()):
    """Teacher-forced reference over each ``(prompt, served)`` pair, by the
    configuration's ``block`` at its sizes ``model``.

    Each sequence (prompt + served tokens but the last) is padded to
    ``pad_to`` tokens (one compiled shape; causal masking keeps the pad out
    of every real position) and read at the ``read_len`` positions that
    predicted the served tokens. Returns, per sequence, the gaps of the
    served tokens below the reference's best logit, and for each lower
    precision in ``controls``, the gaps of that precision's first choices.
    """
    eps = float(model["rms_norm_eps"])
    dev = jax.devices()[0]
    final_norm = jax.device_put(params["final_norm"], dev)
    unembed = jax.device_put(params["unembed"], dev)
    out = []
    with jax.default_matmul_precision("highest"):
        for prompt, served in seqs:
            prompt = np.asarray(prompt, np.int32).reshape(-1)
            served = np.asarray(served, np.int32).reshape(-1)
            seq = np.concatenate([prompt, served[:-1]])
            if len(seq) > pad_to or len(served) > read_len:
                raise ValueError("sequence longer than the reference shape")
            toks = np.zeros(pad_to, np.int32)
            toks[:len(seq)] = seq
            rows = np.zeros(read_len, np.int32)
            rows[:len(served)] = len(prompt) - 1 + np.arange(len(served))
            tgt = np.zeros(read_len, np.int32)
            tgt[:len(served)] = served
            valid = np.arange(read_len) < len(served)
            rows, tgt = jnp.asarray(rows), jnp.asarray(tgt)
            valid = jnp.asarray(valid)
            ref = _head(block.hidden(params, toks, model), final_norm,
                        unembed, rows, eps, None)
            g_served, _ = _gaps(ref, ref, tgt, valid)
            n = len(served)
            ctl = {}
            for quant in controls:
                other = _head(block.hidden(params, toks, model, quant),
                              final_norm, unembed, rows, eps, quant)
                ctl[quant] = np.asarray(_gaps(ref, other, tgt, valid)[1])[:n]
                del other
            out.append((np.asarray(g_served)[:n], ctl))
            del ref
    return out
