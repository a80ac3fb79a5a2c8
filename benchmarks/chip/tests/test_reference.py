"""The plain float32 reference against the paged engine's prefill-then-
decode logits at a tiny size in float32 (``ref`` backend), and the
reference's controls."""
import os
import sys

import numpy as np

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(CHIP, "tests"))

import harness  # noqa: E402
import reference as R  # noqa: E402
import weights as W  # noqa: E402
from tiny_cell import tiny_cell  # noqa: E402


def serve_logits(cell, seed, prompt, n_new):
    """Serve one greedy request; the logits behind each position the
    engine computed, by cache position."""
    import jax.numpy as jnp  # noqa: F401

    _, engine = harness.build(cell, seed, backend="ref",
                              log=lambda _m: None)
    seen = {}

    def prefill(fn):
        def call(p, c, b, nv, tabs):
            out = fn(p, c, b, nv, tabs)
            seen[int(nv) - 1] = np.asarray(out[0][0, -1])
            return out
        return call

    def decode(fn):
        def call(p, c, t, pos, tabs):
            out = fn(p, c, t, pos, tabs)
            seen[int(np.asarray(pos)[0])] = np.asarray(out[0][0, -1])
            return out
        return call

    engine._prefill_paged = prefill(engine._prefill_paged)
    engine._decode_paged = decode(engine._decode_paged)
    req = engine.submit(prompt, n_new)
    engine.run()
    return seen, list(req.out_tokens)


def test_reference_matches_the_paged_engine_in_float32():
    cell = tiny_cell(dtype="float32")
    seed = 12
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, 512, (1, 41), dtype=np.int32)   # 2 blocks + 9
    seen, out = serve_logits(cell, seed, prompt, 10)
    block, model = cell["block"], cell["conf"]["model"]
    params = W.make(block, model, seed)
    seq = np.concatenate([prompt[0], np.asarray(out[:-1], np.int32)])
    x = block.hidden(params, seq, model)
    rows = np.arange(len(seq), dtype=np.int32)
    ref = np.asarray(R._head(x, params["final_norm"], params["unembed"],
                             rows, model["rms_norm_eps"], None))
    # the prefill's last position (31), the 9-token tail riding decode and
    # the generated tokens: every position the engine computed
    assert sorted(seen) == list(range(31, len(seq)))
    scale = np.abs(ref).max()
    for pos, logits in seen.items():
        assert np.abs(logits - ref[pos]).max() <= 1e-4 * scale, pos
    # and the served tokens are the reference's first choices
    gen = ref[len(prompt[0]) - 1:]
    assert [int(i) for i in gen.argmax(-1)] == out


def test_gaps_zero_for_reference_tokens_and_controls_read():
    cell = tiny_cell()
    block, model = cell["block"], cell["conf"]["model"]
    params = W.make(block, model, 3)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, 512, 20, dtype=np.int32)
    # greedy continuation by the reference itself has gap 0 everywhere
    seq = list(prompt)
    for _ in range(6):
        x = block.hidden(params, np.asarray(seq, np.int32), model)
        lg = R._head(x, params["final_norm"], params["unembed"],
                     np.asarray([len(seq) - 1], np.int32),
                     model["rms_norm_eps"], None)
        seq.append(int(np.asarray(lg)[0].argmax()))
    served = seq[len(prompt):]
    (g, ctl), = R.gaps(params, block, model, [(prompt, served)],
                       pad_to=32, read_len=8, controls=("fp8", "int8"))
    assert g.shape == (6,) and np.all(g == 0)
    assert set(ctl) == {"fp8", "int8"}
    assert all(c.shape == (6,) and np.all(c >= 0) for c in ctl.values())
