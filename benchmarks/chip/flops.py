"""Operations and bytes that the served work needs, from the model's
published sizes (a configuration file's ``"model"`` keys). They count what
the algorithm needs for the tokens that are live, whatever kernel or padding
the program uses, so a roofline share computed from them is comparable
across PRs.

Conventions: a multiply-add is 2 operations. Attention over a context of
``c`` positions costs ``4 * Hq * hd * c`` per token and layer (scores and
the weighted sum of values). Causal prefill of ``n`` tokens attends
``n (n + 1) / 2`` positions in all.
"""
from __future__ import annotations

import json
import os
from typing import Iterable, Tuple

HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a device missing from ``peaks.json`` is
    an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       "peaks.json")
    return table[device_kind]


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


def paged_attn_work(m: dict, contexts: Iterable[int], kv_bytes: int = 2,
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


def flash_prefill_work(m: dict, n: int, kv_bytes: int = 2, q_bytes: int = 2,
                       out_bytes: int = 4) -> Tuple[float, float]:
    """(operations, bytes) of causal attention over ``n`` prompt tokens in
    all layers: q, k, v read once, the output written once."""
    L, hq = m["num_hidden_layers"], m["num_attention_heads"]
    hkv, hd = m["num_key_value_heads"], m["head_dim"]
    flops = 4.0 * L * hq * hd * n * (n + 1) / 2
    byts = L * n * hd * (hq * (q_bytes + out_bytes) + 2 * hkv * kv_bytes)
    return flops, float(byts)


def roofline_s(flops: float, byts: float, pk: dict, chips: int = 1
               ) -> float:
    """Least time the chips could take: the larger of operations over
    peak bf16 rate and bytes over peak HBM bandwidth."""
    return max(flops / (pk["bf16_flops"] * chips),
               byts / (pk["hbm_bytes_per_s"] * chips))
