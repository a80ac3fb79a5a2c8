"""A block for the tests only, in the layout the program gives a model with
leading dense layers (``n_dense_layers``): a ``head_layers`` stack of
``first_k_dense`` layers with a feed-forward width of its own
(``dense_intermediate_size``) ahead of the ``layers`` stack. Both stacks
run the dense block's equations (``blocks/dense.py``), so the weights
differ from a dense model's only in their layout. It is one file the
harness loads like any block, and shows that a block with two stacks needs
no edit to a shared file."""
from __future__ import annotations

import importlib.util
import os

import jax.numpy as jnp

import reference as R

CHIP = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_spec = importlib.util.spec_from_file_location(
    "two_stack_dense", os.path.join(CHIP, "blocks", "dense.py"))
D = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(D)


def program_sizes(m: dict) -> dict:
    return {"n_layers": m["num_hidden_layers"],
            "n_dense_layers": m["first_k_dense"],
            "d_model": m["hidden_size"], "d_ff": m["intermediate_size"],
            "n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_key_value_heads"],
            "resolved_head_dim": m["head_dim"],
            "vocab_size": m["vocab_size"], "dtype": m["torch_dtype"]}


def _stack(m: dict, n: int, ff: int) -> dict:
    d, hd = m["hidden_size"], m["head_dim"]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return {"ln1": (n, d), "ln2": (n, d),
            "attn": {"wq": (n, d, hq * hd), "wk": (n, d, hkv * hd),
                     "wv": (n, d, hkv * hd), "wo": (n, hq * hd, d)},
            "mlp": {"wi": (n, d, 2 * ff), "wo": (n, ff, d)}}


def shapes(m: dict) -> dict:
    d, v, k = m["hidden_size"], m["vocab_size"], m["first_k_dense"]
    return {"embed": (v, d), "final_norm": (d,), "unembed": (d, v),
            "head_layers": _stack(m, k, m["dense_intermediate_size"]),
            "layers": _stack(m, m["num_hidden_layers"] - k,
                             m["intermediate_size"])}


def hidden(params, tokens, m: dict, quant=None):
    dims = D.layer_dims(m)
    x = jnp.take(params["embed"], jnp.asarray(tokens), axis=0).astype(
        jnp.float32)
    for stack in ("head_layers", "layers"):
        x = R.over_layers(x, params[stack],
                          lambda x, lp, i: D._layer(x, lp, i, dims, quant))
    return x


def _weights_per_token(m: dict) -> int:
    """Matmul weights every token streams: both stacks and the head."""
    d, hd = m["hidden_size"], m["head_dim"]
    attn = 2 * d * hd * (m["num_attention_heads"]
                         + m["num_key_value_heads"])
    k, n = m["first_k_dense"], m["num_hidden_layers"]
    return (k * (attn + 3 * d * m["dense_intermediate_size"])
            + (n - k) * (attn + 3 * d * m["intermediate_size"])
            + d * m["vocab_size"])


def decode_flops(m: dict, contexts) -> float:
    cs = list(contexts)
    return float(2 * _weights_per_token(m) * len(cs)
                 + 4 * m["num_hidden_layers"] * m["num_attention_heads"]
                 * m["head_dim"] * sum(cs))


def prefill_flops(m: dict, n: int) -> float:
    return float(2 * (_weights_per_token(m) - m["hidden_size"]
                      * m["vocab_size"]) * n
                 + 2 * m["hidden_size"] * m["vocab_size"]
                 + 4 * m["num_hidden_layers"] * m["num_attention_heads"]
                 * m["head_dim"] * n * (n + 1) / 2)


# attention is alike in both stacks: the dense block's count over all layers
def decode_attn_work(m: dict, contexts, kv_bytes: int = 2):
    return D.decode_attn_work(m, contexts, kv_bytes)


def prefill_attn_work(m: dict, n: int, kv_bytes: int = 2):
    return D.prefill_attn_work(m, n, kv_bytes)
