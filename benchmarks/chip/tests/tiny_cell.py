"""A cell at a size the CPU runs in seconds, with the chat mix's shape."""
import copy
import json
import os

import harness

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))


def tiny_cell(tp: int = 1, dtype: str = "bfloat16", limit: float = 0.2):
    """At tp 1 the one-chip chat cell shrunk, comparing ``widest_gap`` at
    ``limit``; at tp > 1 the four-chip cell shrunk, with its own limits."""
    hq, hkv = (8, 4) if tp > 1 else (4, 2)
    model = {"num_hidden_layers": 2, "hidden_size": 128,
             "intermediate_size": 256, "num_attention_heads": hq,
             "num_key_value_heads": hkv, "head_dim": 16, "vocab_size": 512,
             "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
             "tie_word_embeddings": False, "torch_dtype": dtype}
    over = {"n_layers": 2, "d_model": 128, "d_ff": 256, "n_heads": hq,
            "n_kv_heads": hkv, "head_dim": 16, "vocab_size": 512,
            "norm_eps": 1e-5, "dtype": dtype}
    conf = {"block": "dense",
            "repro": {"config": "stablelm-1.6b", "overrides": over},
            "model": model, "chips": tp, "tp": tp,
            "tp_combine": "psum" if tp > 1 else "exact",
            "kv_budget_bytes": 2_000_000, "kv_dtype_bytes": 2}
    with open(os.path.join(CHIP, "traffic", "chat.json")) as f:
        mix = copy.deepcopy(json.load(f))
    mix["generator"]["prompt"].update(median=24, min=8, max=64)
    mix["generator"]["output"].update(median=6, min=2, max=12)
    mix["engine"] = {"n_slots": 4, "max_len": 80, "block_size": 16}
    mix.update(warm_in_s=0.5, drain_cap_s=30, trace_s=1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    # the metrics of the cell this one shrinks
    like = "mistral-nemo-12b-tp4.chat" if tp > 1 else "stablelm-1.6b.chat"
    if tp > 1:
        with open(os.path.join(CHIP, "cells", like + ".json")) as f:
            limits = json.load(f)["limits"]
    else:
        limits = {"widest_gap": limit}
    return {"name": "tiny", "chips": tp, "conf": conf,
            "block": harness.load_block(conf, "tiny_cell"), "mix": mix,
            "own": {"rate_req_s": 4.0, "limits": limits},
            "end_to_end": [m for m in spec["end_to_end"]
                           if like in m.get("workloads", [like])],
            "per_layer": [m for m in spec["per_layer"]
                          if like in m.get("workloads", [like])]}
