"""Plain float32 reference of the served decoder, written from the block's
equations and independent of ``repro.models``.

The block, as the configuration runs it: ``x + attn(norm1(x))`` then
``x + mlp(norm2(x))``, with RMSNorm scaled by ``1 + w``; attention with
rotary positions on the whole head (each head's two halves rotated as a
pair), grouped query heads (query head i reads kv head i // (Hq / Hkv)),
causal softmax at 1 / sqrt(head_dim); a SwiGLU MLP whose input projection
holds the gate and then the up columns; a final RMSNorm and an untied head.
No cache, no kernels, no batching: each sequence runs whole, one layer at a
time, with float32 matmuls at ``highest`` precision. Weights split over
several chips on their layer axis are read one layer at a time on the
first chip, where that layer is upcast.

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


def _mm(x, w, quant):
    w = w.astype(jnp.float32)
    if quant:
        x = _fake_quant(x, -1, quant)
        w = _fake_quant(w, 0, quant)
    return jnp.einsum("...k,kn->...n", x, w,
                      precision=jax.lax.Precision.HIGHEST)


def _norm(x, w, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + eps) * (1.0 + w.astype(jnp.float32))


def _rope(x, theta):
    """x [S, H, hd]: rotate each head's (first half, second half) pair by
    position * theta^(-2i / hd)."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = np.arange(s, dtype=np.float64)[:, None] * inv[None]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[:, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[:, None, :]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def _layer(x, layers, i, dims, quant):
    """One block on x [S, d] (float32), with layer ``i`` of the stacked
    weights upcast to float32 inside the call."""
    d, hq, hkv, hd, eps, theta = dims
    lp = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
        a, i, keepdims=False), layers)
    s = x.shape[0]
    h = _norm(x, lp["ln1"], eps)
    q = _rope(_mm(h, lp["attn"]["wq"], quant).reshape(s, hq, hd), theta)
    k = _rope(_mm(h, lp["attn"]["wk"], quant).reshape(s, hkv, hd), theta)
    v = _mm(h, lp["attn"]["wv"], quant).reshape(s, hkv, hd)
    g = hq // hkv
    k = jnp.repeat(k, g, axis=1)            # query head j reads kv head j//g
    v = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k,
                        precision=jax.lax.Precision.HIGHEST) / np.sqrt(hd)
    causal = np.tril(np.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("hqk,khd->qhd", probs, v,
                     precision=jax.lax.Precision.HIGHEST).reshape(s, hq * hd)
    x = x + _mm(att, lp["attn"]["wo"], quant)
    h = _norm(x, lp["ln2"], eps)
    gu = _mm(h, lp["mlp"]["wi"], quant)
    ff = gu.shape[-1] // 2
    act = jax.nn.silu(gu[:, :ff]) * gu[:, ff:]
    return x + _mm(act, lp["mlp"]["wo"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head(x, final_norm, unembed, rows, eps, quant):
    """Logits [P, V] at positions ``rows`` of x [S, d]."""
    h = _norm(x[rows], final_norm, eps)
    return _mm(h, unembed, quant)


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


def dims_of(model: dict) -> tuple:
    return (model["hidden_size"], model["num_attention_heads"],
            model["num_key_value_heads"], model["head_dim"],
            float(model["rms_norm_eps"]), float(model["rope_theta"]))


def _spread(params) -> bool:
    """Whether the weights lie on more than one device."""
    return len(params["layers"]["ln1"].sharding.device_set) > 1


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


def hidden(params, tokens, dims, quant=None):
    """Final hidden states [S, d] of one sequence ``tokens`` [S]. Weights
    spread over several devices (stacked leaves split on the layer axis)
    are read one layer at a time on the first of them."""
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(
        jnp.float32)
    n_layers = params["layers"]["ln1"].shape[0]
    if not _spread(params):
        for i in range(n_layers):
            x = _layer(x, params["layers"], jnp.int32(i), dims, quant)
        return x
    dev = jax.devices()[0]
    x = jax.device_put(x, dev)
    for i in range(n_layers):
        x = _layer(x, _layer_to(params["layers"], i, dev), jnp.int32(0),
                   dims, quant)
    return x


def gaps(params, model: dict, seqs, pad_to: int, read_len: int,
         controls=()):
    """Teacher-forced reference over each ``(prompt, served)`` pair.

    Each sequence (prompt + served tokens but the last) is padded to
    ``pad_to`` tokens (one compiled shape; causal masking keeps the pad out
    of every real position) and read at the ``read_len`` positions that
    predicted the served tokens. Returns, per sequence, the gaps of the
    served tokens below the reference's best logit, and for each lower
    precision in ``controls``, the gaps of that precision's first choices.
    """
    dims = dims_of(model)
    eps = dims[4]
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
            ref = _head(hidden(params, toks, dims), final_norm, unembed,
                        rows, eps, None)
            g_served, _ = _gaps(ref, ref, tgt, valid)
            n = len(served)
            ctl = {}
            for quant in controls:
                other = _head(hidden(params, toks, dims, quant),
                              final_norm, unembed, rows, eps, quant)
                ctl[quant] = np.asarray(_gaps(ref, other, tgt, valid)[1])[:n]
                del other
            out.append((np.asarray(g_served)[:n], ctl))
            del ref
    return out
