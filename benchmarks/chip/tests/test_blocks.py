"""The seam between the harness and a configuration's block: a
configuration that names no block, or one with no file, is refused where
the cell is loaded, with the file named; the readers count through the
cell's block; and the reference stays independent of the program (no
block and not ``reference.py`` imports ``repro``)."""
import ast
import json
import os
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)

import flops as F  # noqa: E402
import harness  # noqa: E402

TWO_STACK = os.path.join(CHIP, "tests", "fixtures", "two_stack.py")


@pytest.mark.parametrize("block,says", [(None, "names no block"),
                                        ("nope", "block 'nope' has no file")])
def test_configuration_without_a_known_block_is_refused(tmp_path, block,
                                                        says):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    conf_file = os.path.join(CHIP, "configs", "stablelm-1.6b.json")
    with open(conf_file) as f:
        conf = json.load(f)
    conf.pop("block")
    if block:
        conf["block"] = block
    path = tmp_path / "configs" / "x.json"
    path.parent.mkdir()
    path.write_text(json.dumps(conf))
    for c in spec["configs"]:
        c["file"] = "configs/x.json"
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    with pytest.raises(ValueError) as e:
        harness.load_cell("stablelm-1.6b.chat",
                          bench=str(tmp_path / "BENCHMARK.json"))
    assert says in str(e.value) and str(path) in str(e.value)


def test_both_cells_load_the_dense_block():
    for name in ("stablelm-1.6b.chat", "mistral-nemo-12b-tp4.chat"):
        cell = harness.load_cell(name)
        assert cell["block"] is harness.load_module(
            os.path.join(CHIP, "blocks", "dense.py"))


def _read(reader, rec):
    return harness.load_module(os.path.join(CHIP, "metrics",
                                            reader + ".py")).read(rec)


@pytest.mark.parametrize("reader", ["decode_mfu", "prefill_mfu",
                                    "paged_attn_roofline",
                                    "flash_prefill_roofline"])
def test_readers_count_through_the_cells_block(reader):
    """The two-stack block read on a made-up trace: at one feed-forward
    width in both stacks it counts as the dense block does; a wider head
    stack adds weights, which the MFU readers see and the attention
    readers do not."""
    dense = harness.load_module(os.path.join(CHIP, "blocks", "dense.py"))
    two = harness.load_module(TWO_STACK)
    m = {"num_hidden_layers": 5, "first_k_dense": 1, "hidden_size": 128,
         "intermediate_size": 256, "dense_intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
         "vocab_size": 512}
    trace = {"devices": [{"name": "/device:TPU:0", "modules": [
        ["jit_decode(1)", 0, 100], ["jit_prefill(2)", 200, 300]],
        "ops": [["paged_decode_attention", 10, 40],
                ["flash_prefill_attention", 210, 250]]}],
        "spans": [], "window": [0, 1000]}
    rec = {"model": m, "chips": 1, "kv_bytes": 2, "trace": trace,
           "peaks": F.peaks("TPU v5 lite"), "decode_calls": [[30, 70]],
           "prefill_calls": [48]}
    same = _read(reader, dict(rec, block=dense))
    assert same > 0
    assert _read(reader, dict(rec, block=two)) == pytest.approx(same,
                                                                rel=1e-12)
    wider = _read(reader, dict(rec, block=two, model=dict(
        m, dense_intermediate_size=512)))
    if reader.endswith("mfu"):
        assert wider > same
    else:
        assert wider == pytest.approx(same, rel=1e-12)


def _imports(path):
    names = set()
    for node in ast.walk(ast.parse(open(path).read(), path)):
        if isinstance(node, ast.Import):
            names |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom) and node.module:
            names.add(node.module.split(".")[0])
    return names


def test_reference_and_blocks_import_nothing_of_the_program():
    blocks = os.path.join(CHIP, "blocks")
    paths = [os.path.join(CHIP, "reference.py"), TWO_STACK] + [
        os.path.join(blocks, f) for f in sorted(os.listdir(blocks))
        if f.endswith(".py")]
    assert len(paths) >= 3
    local = {os.path.splitext(f)[0] for f in os.listdir(CHIP)
             if f.endswith(".py")}
    for path in paths:
        names = _imports(path)
        assert "repro" not in names, path
        # of the benchmark's own modules a block reads only the reference
        assert names & local <= {"reference"}, path
