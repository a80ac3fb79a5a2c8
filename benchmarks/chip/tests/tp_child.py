"""Run in a child process on four host devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``; the tests that
read its line start it): the harness's tensor-parallel path at a tiny size.
Prints one JSON line of what ``test_tp_cpu.py`` asserts."""
import json
import os
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(CHIP, "tests"))

import harness  # noqa: E402
import reference as R  # noqa: E402
import weights as W  # noqa: E402
from tiny_cell import tiny_cell  # noqa: E402

SEED = 2**33 + 21
#: the test-only block with a head_layers stack ahead of layers
TWO_STACK = os.path.join(CHIP, "tests", "fixtures", "two_stack.py")
TWO_STACK_MODEL = {"num_hidden_layers": 5, "first_k_dense": 1,
                   "hidden_size": 128, "intermediate_size": 256,
                   "dense_intermediate_size": 384, "num_attention_heads": 4,
                   "num_key_value_heads": 2, "head_dim": 16,
                   "vocab_size": 512, "rope_theta": 10000.0,
                   "rms_norm_eps": 1e-5, "torch_dtype": "bfloat16"}


def placements(cell) -> dict:
    """The weights made as the program serves them, as the reference reads
    them, and on one device: equal bit for bit."""
    import jax
    import numpy as np

    block, model = cell["block"], cell["conf"]["model"]
    abstract = W.abstract(block, model)
    one = W.make(block, model, SEED)
    tp = W.make(block, model, SEED,
                harness.tp_shardings(abstract, cell["conf"]))
    layer = W.make(block, model, SEED,
                   harness.reference_shardings(abstract, cell["chips"]))
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               and np.array_equal(np.asarray(a), np.asarray(c))
               for a, b, c in zip(jax.tree.leaves(one), jax.tree.leaves(tp),
                                  jax.tree.leaves(layer)))
    devs = {k: len(v.sharding.device_set) for k, v in (
        ("tp.wq", tp["layers"]["attn"]["wq"]),
        ("layer.wq", layer["layers"]["attn"]["wq"]),
        ("layer.embed", layer["embed"]))}
    # the reference over one device and over the layer-split weights
    rng = np.random.default_rng(3)
    seqs = [(rng.integers(0, 512, 20, dtype=np.int32),
             rng.integers(0, 512, 5, dtype=np.int32))]
    g1 = R.gaps(one, block, model, seqs, pad_to=32, read_len=8,
                controls=("fp8",))
    g2 = R.gaps(layer, block, model, seqs, pad_to=32, read_len=8,
                controls=("fp8",))
    gaps_same = (np.array_equal(g1[0][0], g2[0][0])
                 and np.array_equal(g1[0][1]["fp8"], g2[0][1]["fp8"]))
    return {"bit_identical": bool(same), "devices": devs,
            "reference_same": bool(gaps_same)}


def two_stack() -> dict:
    """The two-stack block's weights made on one device and as the
    reference takes them over four (``layers`` split on its layer axis,
    the one-layer ``head_layers`` whole on each), and the reference read
    whole on one device and a layer at a time from the four."""
    import jax
    import numpy as np

    block, model = harness.load_module(TWO_STACK), TWO_STACK_MODEL
    one = W.make(block, model, SEED)
    spread = W.make(block, model, SEED, harness.reference_shardings(
        W.abstract(block, model), 4))
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(one), jax.tree.leaves(spread)))
    rng = np.random.default_rng(4)
    seqs = [(rng.integers(0, 512, 20, dtype=np.int32),
             rng.integers(0, 512, 5, dtype=np.int32))]
    g1 = R.gaps(one, block, model, seqs, pad_to=32, read_len=8,
                controls=("fp8",))
    g2 = R.gaps(spread, block, model, seqs, pad_to=32, read_len=8,
                controls=("fp8",))

    def held(a):
        return [a.sharding.is_fully_replicated,
                sorted(s.data.shape[0] for s in a.addressable_shards)]
    return {"stacks": sorted(k for k, v in one.items() if isinstance(v, dict)),
            "ff": [one[k]["mlp"]["wo"].shape[1]
                   for k in ("head_layers", "layers")],
            "bit_identical": bool(same),
            "held": {k: held(spread[k]["attn"]["wq"])
                     for k in ("head_layers", "layers")},
            "gaps": g1[0][0].tolist(),
            "reference_same": bool(
                np.array_equal(g1[0][0], g2[0][0])
                and np.array_equal(g1[0][1]["fp8"], g2[0][1]["fp8"]))}


def no_exchange(cell) -> dict:
    """The same run with the exchange between chips left out: each chip's
    row-parallel output projections keep their own partial sums (the
    ``psum`` that completes them returns its input)."""
    import jax

    psum = jax.lax.psum
    jax.lax.psum = lambda x, *_a, **_k: x
    try:
        return harness.run(cell, SEED, 3.0, False, t_start=harness.clock(),
                           require_tpu=False, backend="ref", cache=False,
                           log=lambda _m: None)
    finally:
        jax.lax.psum = psum


def served(cell) -> dict:
    """One run of the tp=2 cell on the ``ref`` backend, and how the engine
    it built holds its weights and pools."""
    import jax

    seen = {}
    build = harness.build

    def spy(*args, **kw):
        cfg, engine = build(*args, **kw)
        wq = engine.params["layers"]["attn"]["wq"]
        seen.update(
            backend=engine.backend.name, tp=engine.tp,
            wq_shard_cols=[s.data.shape[-1] for s in wq.addressable_shards],
            wq_cols=wq.shape[-1],
            pool_heads=sorted({s.data.shape[3]
                               for leaf in jax.tree.leaves(engine.kv.pools)
                               for s in leaf.addressable_shards}))
        return cfg, engine

    harness.build = spy
    out = harness.run(cell, SEED, 3.0, False, t_start=harness.clock(),
                      require_tpu=False, backend="ref", cache=False,
                      log=lambda _m: None)
    harness.build = build
    return {"out": out, "engine": seen}


def main() -> int:
    import jax

    cell = tiny_cell(tp=2)
    print(json.dumps({"n_devices": len(jax.devices()),
                      "placements": placements(cell),
                      "two_stack": two_stack(),
                      "served": served(cell),
                      "no_exchange": no_exchange(cell)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
