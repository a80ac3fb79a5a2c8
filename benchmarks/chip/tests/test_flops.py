"""Operation and byte counts of the dense block (the one stablelm-1.6b's
configuration names) against hand counts at its published widths."""
import json
import os
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

import flops as F  # noqa: E402
import harness  # noqa: E402

CONF = os.path.join(CHIP, "configs", "stablelm-1.6b.json")
with open(CONF) as f:
    CONF_JSON = json.load(f)
M = CONF_JSON["model"]
B = harness.load_block(CONF_JSON, CONF)

# one layer: q and o 2048 x 2048 each, k and v 2048 x 2048 each (32 kv
# heads of 64), the SwiGLU 3 x 2048 x 5632
LAYER = 2 * 2048 * 2048 + 2 * 2048 * 2048 + 3 * 2048 * 5632
HEAD = 2048 * 100352
ATTN = 4 * 24 * 32 * 64          # per token and attended position


def test_layer_params():
    assert B.layer_params(M) == LAYER == 51_380_224
    # 24 layers and the head: 1.44 B weights that each token streams
    assert 24 * LAYER + HEAD == 1_233_125_376 + 205_520_896


def test_decode_flops():
    assert B.decode_flops(M, [1]) == 2 * 24 * LAYER + 2 * HEAD + ATTN
    assert B.decode_flops(M, [100, 300]) == \
        2 * (2 * 24 * LAYER + 2 * HEAD) + ATTN * 400
    assert B.decode_flops(M, []) == 0


def test_prefill_flops():
    n = 512
    assert B.prefill_flops(M, n) == \
        2 * 24 * LAYER * n + ATTN * n * (n + 1) / 2 + 2 * HEAD


def test_paged_attn_work():
    fl, by = B.decode_attn_work(M, [100, 28], kv_bytes=2)
    assert fl == ATTN * 128
    # K and V of 128 live positions, 32 kv heads of 64, bf16, per layer;
    # plus each live row's bf16 query and f32 output
    assert by == 24 * (128 * 2 * 32 * 64 * 2 + 2 * 32 * 64 * (2 + 4))


def test_flash_prefill_work():
    fl, by = B.prefill_attn_work(M, 256, kv_bytes=2)
    assert fl == ATTN * 256 * 257 / 2
    assert by == 24 * 256 * 64 * (32 * (2 + 4) + 2 * 32 * 2)


def test_roofline_and_peaks():
    pk = F.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    # memory-bound: 819 MB at 819 GB/s is a millisecond
    assert F.roofline_s(1e9, 819e6, pk) == pytest.approx(1e-3)
    assert F.roofline_s(197e12, 0.0, pk, chips=4) == pytest.approx(0.25)
    with pytest.raises(KeyError):
        F.peaks("TPU v4")
