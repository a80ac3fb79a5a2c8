"""The harness's tensor-parallel path at a tiny size, on four host devices
in a child process (``tp_child.py``; in-process tests see one CPU device):
a tp=2 cell served on ``ref`` (``ref-tp``) comes out correct with its
weights and pools split over two devices; the weights are the same bit for
bit however they are placed; the reference reads the same gaps from
layer-split weights, also for a block with two stacks; leaving out the
exchange between chips comes out as not correct. And
``tp_collective_ms_per_step`` on made-up traces."""
import json
import os
import subprocess
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path.insert(0, CHIP)

import harness  # noqa: E402


@pytest.fixture(scope="module")
def child():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable,
                        os.path.join(CHIP, "tests", "tp_child.py")],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["n_devices"] == 4
    return out


def test_tp2_cell_is_served_sharded_and_correct(child):
    s = child["served"]
    assert s["engine"]["backend"] == "ref-tp" and s["engine"]["tp"] == 2
    # wq split by column over two devices; each pool leaf holds 4 / 2 heads
    assert s["engine"]["wq_shard_cols"] == [s["engine"]["wq_cols"] // 2] * 2
    assert s["engine"]["pool_heads"] == [2]
    out = s["out"]
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == 4
    assert out["check"]["short_requests"]["value"] == 0
    # the four-chip cell's own numbers are compared
    assert set(out["check"]) == {"mean_gap", "short_requests"}


def test_weights_bit_identical_however_placed(child):
    p = child["placements"]
    assert p["bit_identical"] is True
    assert p["devices"] == {"tp.wq": 2, "layer.wq": 2, "layer.embed": 2}


def test_reference_reads_layer_split_weights_alike(child):
    assert child["placements"]["reference_same"] is True


def test_two_stack_block_spread_reads_as_whole(child):
    # a block with a head_layers stack of another feed-forward width ahead
    # of layers: the same weights however placed, layers split one a
    # device, the one head layer whole on each, and the reference read a
    # layer at a time from four devices as from one
    t = child["two_stack"]
    assert t["stacks"] == ["head_layers", "layers"] and t["ff"] == [384, 256]
    assert t["bit_identical"] is True
    assert t["held"] == {"head_layers": [True, [1] * 4],
                         "layers": [False, [1] * 4]}
    assert len(t["gaps"]) == 5 and all(g >= 0 for g in t["gaps"])
    assert t["reference_same"] is True


def test_exchange_between_chips_left_out_is_not_correct(child):
    # judged by the number the four-chip cell compares, at its limit
    out = child["no_exchange"]
    assert out["correct"] is False
    assert out["check"]["mean_gap"]["value"] > \
        out["check"]["mean_gap"]["limit"] == harness.load_cell(
            "mistral-nemo-12b-tp4.chat")["own"]["limits"]["mean_gap"]


def test_tp1_engine_kwargs_name_no_tp():
    cell = harness.load_cell("stablelm-1.6b.chat")
    kw = harness._engine_kwargs(cell, None)
    assert "tp" not in kw and "tp_combine" not in kw
    assert harness.tp_shardings(None, cell["conf"]) is None
    assert harness.reference_shardings(None, 1) is None
    nemo = harness.load_cell("mistral-nemo-12b-tp4.chat")
    kw = harness._engine_kwargs(nemo, None)
    assert (kw["tp"], kw["tp_combine"]) == (4, "psum")


def _summary(collective):
    # a decode program [0, 100] with the paged kernel and two collectives,
    # a prefill program [200, 300] with one; on two devices
    ops = [["paged_decode_attention", 10, 40], [collective, 50, 56],
           [collective, 60, 64], ["flash_prefill_attention", 210, 250],
           [collective, 260, 290]]
    dev = {"modules": [["jit_decode_step_paged(1)", 0, 100],
                       ["jit_prefill_paged(2)", 200, 300]], "ops": ops}
    return {"devices": [dict(dev, name="/device:TPU:0"),
                        dict(dev, name="/device:TPU:1")],
            "spans": [], "window": [0, 1000]}


def _reader():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "tp_collective", os.path.join(CHIP, "metrics",
                                      "tp_collective_ms_per_step.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("name", ["all-reduce", "all-reduce-start",
                                  "all-gather", "collective-permute-done"])
def test_tp_collective_reads_decode_collectives(name):
    # 10 ns of collectives in the one decode step; the prefill's left out
    assert _reader()({"trace": _summary(name)}) == pytest.approx(10e-6)


def test_tp_collective_silent_without_collectives():
    read = _reader()
    assert read({"trace": _summary("fusion")}) is None
    assert read({"trace": None}) is None
