"""The dense grouped-query decoder block: everything about the architecture
that the harness needs, from a configuration file's ``"model"`` keys (the
published sizes). A configuration names its block (``"block": "dense"``);
the harness loads ``blocks/<block>.py`` by file path and asks it for:

    program_sizes(model)   {ModelConfig attribute: value} the program's
                           config is checked against
    shapes(model)          the weight pytree as shapes, in the program's
                           layout (top-level arrays, and stacks of layers:
                           groups whose leaves all lead with the layer axis)
    hidden(params, tokens, model, quant)
                           the plain float32 forward to the final hidden
                           states (``reference.gaps`` applies the head)
    decode_flops, prefill_flops, decode_attn_work, prefill_attn_work
                           operations and bytes the served work needs

The block, as the configuration runs it: ``x + attn(norm1(x))`` then
``x + mlp(norm2(x))``, with RMSNorm scaled by ``1 + w``; attention with
rotary positions on the whole head (each head's two halves rotated as a
pair), grouped query heads (query head i reads kv head i // (Hq / Hkv)),
causal softmax at 1 / sqrt(head_dim); a SwiGLU MLP whose input projection
holds the gate and then the up columns. The reference is written from these
equations, imports nothing of the program, and runs each sequence whole,
one layer at a time, with float32 matmuls at ``highest`` precision.

Counts: a multiply-add is 2 operations. Attention over a context of ``c``
positions costs ``4 * Hq * hd * c`` per token and layer (scores and the
weighted sum of values). Causal prefill of ``n`` tokens attends
``n (n + 1) / 2`` positions in all. They count what the algorithm needs
for the tokens that are live, whatever kernel or padding the program uses.
"""
from __future__ import annotations

import functools
from typing import Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import reference as R


def program_sizes(m: dict) -> dict:
    return {"n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
            "d_ff": m["intermediate_size"],
            "n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_key_value_heads"],
            "resolved_head_dim": m["head_dim"],
            "vocab_size": m["vocab_size"], "rope_theta": m["rope_theta"],
            "norm_eps": m["rms_norm_eps"], "dtype": m["torch_dtype"],
            "tie_embeddings": m["tie_word_embeddings"]}


def shapes(m: dict) -> dict:
    d, hd, L = m["hidden_size"], m["head_dim"], m["num_hidden_layers"]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    ff, v = m["intermediate_size"], m["vocab_size"]
    return {
        "embed": (v, d), "final_norm": (d,), "unembed": (d, v),
        "layers": {
            "ln1": (L, d), "ln2": (L, d),
            "attn": {"wq": (L, d, hq * hd), "wk": (L, d, hkv * hd),
                     "wv": (L, d, hkv * hd), "wo": (L, hq * hd, d)},
            "mlp": {"wi": (L, d, 2 * ff), "wo": (L, ff, d)},
        },
    }


# ------------------------------------------------------------------ #
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
    h = R.norm(x, lp["ln1"], eps)
    q = _rope(R.mm(h, lp["attn"]["wq"], quant).reshape(s, hq, hd), theta)
    k = _rope(R.mm(h, lp["attn"]["wk"], quant).reshape(s, hkv, hd), theta)
    v = R.mm(h, lp["attn"]["wv"], quant).reshape(s, hkv, hd)
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
    x = x + R.mm(att, lp["attn"]["wo"], quant)
    h = R.norm(x, lp["ln2"], eps)
    gu = R.mm(h, lp["mlp"]["wi"], quant)
    ff = gu.shape[-1] // 2
    act = jax.nn.silu(gu[:, :ff]) * gu[:, ff:]
    return x + R.mm(act, lp["mlp"]["wo"], quant)


def layer_dims(m: dict) -> tuple:
    """The static sizes ``_layer`` takes."""
    return (m["hidden_size"], m["num_attention_heads"],
            m["num_key_value_heads"], m["head_dim"],
            float(m["rms_norm_eps"]), float(m["rope_theta"]))


def hidden(params, tokens, m: dict, quant=None):
    """Final hidden states [S, d] of one sequence ``tokens`` [S]."""
    dims = layer_dims(m)
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(
        jnp.float32)
    return R.over_layers(x, params["layers"],
                         lambda x, lp, i: _layer(x, lp, i, dims, quant))


# ------------------------------------------------------------------ #
def layer_params(m: dict) -> int:
    """Matmul weights of one decoder layer (q, k, v, o and the SwiGLU)."""
    d, hd = m["hidden_size"], m["head_dim"]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return d * hq * hd * 2 + 2 * d * hkv * hd + 3 * d * m["intermediate_size"]


def decode_flops(m: dict, contexts: Iterable[int]) -> float:
    """One decode step: each live sequence adds one token and attends its
    ``c`` positions (the new one included)."""
    per_tok = 2 * m["num_hidden_layers"] * layer_params(m) \
        + 2 * m["hidden_size"] * m["vocab_size"]
    attn = 4 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * m["head_dim"]
    cs = list(contexts)
    return float(len(cs) * per_tok + attn * sum(cs))


def prefill_flops(m: dict, n: int) -> float:
    """Prefill of ``n`` prompt tokens; the head runs on the last one."""
    attn = 4 * m["num_hidden_layers"] * m["num_attention_heads"] \
        * m["head_dim"]
    return float(2 * m["num_hidden_layers"] * layer_params(m) * n
                 + attn * n * (n + 1) / 2
                 + 2 * m["hidden_size"] * m["vocab_size"])


def decode_attn_work(m: dict, contexts: Iterable[int], kv_bytes: int = 2,
                     q_bytes: int = 2, out_bytes: int = 4
                     ) -> Tuple[float, float]:
    """(operations, bytes) of decode attention over all layers: each live
    sequence reads K and V of its ``c`` positions once, and its query and
    output rows."""
    L, hq = m["num_hidden_layers"], m["num_attention_heads"]
    hkv, hd = m["num_key_value_heads"], m["head_dim"]
    cs = list(contexts)
    flops = 4.0 * L * hq * hd * sum(cs)
    byts = L * (sum(cs) * 2 * hkv * hd * kv_bytes
                + len(cs) * hq * hd * (q_bytes + out_bytes))
    return flops, float(byts)


def prefill_attn_work(m: dict, n: int, kv_bytes: int = 2, q_bytes: int = 2,
                      out_bytes: int = 4) -> Tuple[float, float]:
    """(operations, bytes) of causal attention over ``n`` prompt tokens in
    all layers: q, k, v read once, the output written once."""
    L, hq = m["num_hidden_layers"], m["num_attention_heads"]
    hkv, hd = m["num_key_value_heads"], m["head_dim"]
    flops = 4.0 * L * hq * hd * n * (n + 1) / 2
    byts = L * n * hd * (hq * (q_bytes + out_bytes) + 2 * hkv * kv_bytes)
    return flops, float(byts)
