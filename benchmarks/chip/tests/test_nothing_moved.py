"""What the benchmark reads stays as it was before the architecture moved
into ``blocks/``: the values here were taken from the harness as it stood
with the dense block built into ``weights.py``, ``reference.py`` and
``flops.py``, and the harness of today, through the block each
configuration names, has to give them again exactly. The shape tree of both
configurations at their published widths, a checksum of the weights made
from one seed at a tiny size, the reference's gaps at that size, and each
reader of an operation count on the recorded trace."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(CHIP, "tests"))

import flops as F  # noqa: E402
import harness  # noqa: E402
import reference as R  # noqa: E402
import trace_reduce as T  # noqa: E402
import weights as W  # noqa: E402
from tiny_cell import tiny_cell  # noqa: E402

SHAPES = {
    "stablelm-1.6b": (1644267520, [
        ("['embed']", (100352, 2048)),
        ("['final_norm']", (2048,)),
        ("['layers']['attn']['wk']", (24, 2048, 2048)),
        ("['layers']['attn']['wo']", (24, 2048, 2048)),
        ("['layers']['attn']['wq']", (24, 2048, 2048)),
        ("['layers']['attn']['wv']", (24, 2048, 2048)),
        ("['layers']['ln1']", (24, 2048)),
        ("['layers']['ln2']", (24, 2048)),
        ("['layers']['mlp']['wi']", (24, 2048, 11264)),
        ("['layers']['mlp']['wo']", (24, 5632, 2048)),
        ("['unembed']", (2048, 100352)),
    ]),
    "mistral-nemo-12b-tp4": (12247782400, [
        ("['embed']", (131072, 5120)),
        ("['final_norm']", (5120,)),
        ("['layers']['attn']['wk']", (40, 5120, 1024)),
        ("['layers']['attn']['wo']", (40, 4096, 5120)),
        ("['layers']['attn']['wq']", (40, 5120, 4096)),
        ("['layers']['attn']['wv']", (40, 5120, 1024)),
        ("['layers']['ln1']", (40, 5120)),
        ("['layers']['ln2']", (40, 5120)),
        ("['layers']['mlp']['wi']", (40, 5120, 28672)),
        ("['layers']['mlp']['wo']", (40, 14336, 5120)),
        ("['unembed']", (5120, 131072)),
    ]),
}
SEED = 2**33 + 5
CHECKSUM = "f294cb6d4296be6f6d8185023204340326cba47ce599aff6c8b926edad7171ef"
#: per sequence: the served tokens' gaps, then fp8's and int8's
GAPS = [
    ([3.1988513469696045, 2.282038688659668, 3.766458034515381,
      2.07049822807312, 3.4911341667175293, 2.9013638496398926],
     {"fp8": [0.0, 0.004089832305908203, 0.0, 0.0, 0.0, 0.0],
      "int8": [0.0, 0.0, 0.0, 0.0, 0.0, 0.0]}),
    ([1.5631927251815796, 4.00412654876709, 4.666996002197266],
     {"fp8": [0.0, 0.015293598175048828, 0.0],
      "int8": [0.0, 0.015293598175048828, 0.0]}),
]
READERS = {
    "stablelm-1.6b": {
        "decode_mfu": 0.028322481576429026,
        "prefill_mfu": 16.35725332791878,
        "paged_attn_roofline": 0.5920742440332816,
        "flash_prefill_roofline": 1.5363750915750916
    },
    "mistral-nemo-12b-tp4": {
        "decode_mfu": 0.054907267406063415,
        "prefill_mfu": 35.70534348020305,
        "paged_attn_roofline": 0.12392207179692198,
        "flash_prefill_roofline": 0.8962188034188033
    }
}


def _conf(name):
    path = os.path.join(CHIP, "configs", name + ".json")
    with open(path) as f:
        conf = json.load(f)
    return conf, harness.load_block(conf, path)


@pytest.mark.parametrize("name", sorted(SHAPES))
def test_shape_tree_at_published_widths(name):
    import jax

    conf, block = _conf(name)
    # the program's config agrees with the sizes the block reads
    harness.model_config(conf, block)
    tree = W.abstract(block, conf["model"])
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    got = [(jax.tree_util.keystr(p), a.shape) for p, a in flat]
    total, want = SHAPES[name]
    assert got == want
    assert sum(int(np.prod(s)) for _, s in got) == total


def test_weights_checksum_at_a_tiny_size():
    import jax

    cell = tiny_cell()
    params = W.make(cell["block"], cell["conf"]["model"], SEED)
    h = hashlib.sha256()
    for p, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        a = np.asarray(a)
        h.update(f"{jax.tree_util.keystr(p)}{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    assert h.hexdigest() == CHECKSUM


def test_reference_gaps_at_a_tiny_size():
    cell = tiny_cell()
    model = cell["conf"]["model"]
    params = W.make(cell["block"], model, SEED)
    rng = np.random.default_rng(5)
    seqs = [(rng.integers(0, 512, 20, dtype=np.int32),
             rng.integers(0, 512, 6, dtype=np.int32)),
            (rng.integers(0, 512, 9, dtype=np.int32),
             rng.integers(0, 512, 3, dtype=np.int32))]
    out = R.gaps(params, cell["block"], model, seqs, pad_to=32, read_len=8,
                 controls=("fp8", "int8"))
    for (g, ctl), (want, want_ctl) in zip(out, GAPS):
        assert np.array_equal(g, np.asarray(want, np.float32))
        assert set(ctl) == set(want_ctl)
        for q, c in ctl.items():
            assert np.array_equal(c, np.asarray(want_ctl[q], np.float32)), q


def recorded_trace():
    """The recorded decode steps, with a prefill program (a flash kernel
    20 ms of its 40) put after them, so that both kinds of reader read."""
    trace = T.read(os.path.join(CHIP, "tests", "fixtures",
                                "decode_steps.json.gz"))
    dev = trace["devices"][0]
    dev["modules"].append(["jit_prefill_paged(1)", 1000000000, 1040000000])
    dev["ops"] += [["fusion", 1000000000, 1010000000],
                   ["flash_prefill_attention", 1010000000, 1030000000]]
    return trace


@pytest.mark.parametrize("reader", ["decode_mfu", "prefill_mfu",
                                    "paged_attn_roofline",
                                    "flash_prefill_roofline"])
@pytest.mark.parametrize("name", sorted(READERS))
def test_count_readers_on_the_recorded_trace(name, reader):
    conf, block = _conf(name)
    rec = {"model": conf["model"], "block": block, "chips": conf["chips"],
           "kv_bytes": 2, "peaks": F.peaks("TPU v5 lite"),
           "trace": recorded_trace(),
           "decode_calls": [[17, 300, 1500, 2048], [18, 301, 1501, 2049]],
           "prefill_calls": [512]}
    read = harness.load_module(os.path.join(CHIP, "metrics",
                                            reader + ".py")).read
    assert read(rec) == READERS[name][reader]
