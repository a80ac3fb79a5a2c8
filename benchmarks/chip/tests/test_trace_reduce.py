"""The trace reduction on a small recorded trace (two decode steps of
stablelm-1.6b at the chat cell's sizes on a v5e chip) and on a hand-made
one whose answers are counted by hand."""
import os
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)

import trace_reduce as T  # noqa: E402

FIXTURE = os.path.join(CHIP, "tests", "fixtures", "decode_steps.json.gz")


def hand():
    # ns: a prefill program [0, 40] holding the flash kernel [10, 30]; a
    # decode program [50, 90] holding a while [52, 88] with the paged
    # kernel [55, 75] and an all-reduce [76, 80] inside; host spans
    dev = {"name": "/device:TPU:0",
           "modules": [["jit__lambda(1)", 0, 40], ["jit__lambda(2)", 50, 90],
                       ["jit_add(3)", 95, 97]],
           "ops": [["fusion", 0, 10], ["flash_prefill_attention", 10, 30],
                   ["copy", 30, 40], ["while", 52, 88],
                   ["paged_decode_attention", 55, 75],
                   ["all-reduce", 76, 80], ["add", 95, 97]]}
    spans = [["bench.step", 0, 100], ["bench.admit", 0, 45],
             ["bench.prefill", 0, 42], ["bench.decode", 46, 49],
             ["bench.traced", 0, 100]]
    return {"devices": [dev], "spans": spans, "window": [0, 100]}


def test_op_name():
    assert T.op_name("%paged_decode_attention.9 = f32[32,32,1,64]{3,2,1,0} "
                     "custom-call(s32[32,176] %copy-done)") == \
        "paged_decode_attention"
    assert T.op_name("%copy.63 = bf16[1,1430] copy(bf16[1] %x)") == "copy"
    assert T.op_name("%constant_dynamic-slice_fusion.12 = bf16[1]") == \
        "constant_dynamic-slice_fusion"
    assert T.op_name("%all-reduce.3 = f32[8]") == "all-reduce"


def test_collective_named_by_its_operation():
    # JAX names an all-reduce after its primitive; the trace's text of the
    # instruction carries the operation
    assert T.op_name("%psum.36 = bf16[32,1,5120]{2,0,1} all-reduce("
                     "bf16[32,1,5120] %x), channel_id=1") == "all-reduce"
    assert T.op_name("%ar.2 = (bf16[8], bf16[8]) all-reduce-start("
                     "bf16[8] %a)") == "all-reduce-start"
    assert T.op_name("%all-gather.1 = bf16[8,4] all-gather(bf16[8,2] %a)") \
        == "all-gather"
    # an operand named after a collective does not make a fusion one
    assert T.op_name("%fusion.3 = bf16[8] fusion(bf16[8] %all-reduce.3)") \
        == "fusion"
    assert T.op_name("psum.36") == "psum"


def test_busy_union_and_idle_by_hand():
    s = hand()
    busy, window = T.busy_share(s)
    # busy: [0, 40] + [52, 88] + [95, 97] = 78 ns of 100
    assert busy == pytest.approx(78e-9) and window == pytest.approx(100e-9)
    # gaps: [40, 52] mid 46 is in bench.decode; [88, 95] mid 91 only in
    # the step (harvest); [97, 100] mid 98 harvest
    assert T.idle_by_label(s) == pytest.approx(
        {"decode call": 12e-9, "harvest": 10e-9})
    assert T.idle_gaps(s, 2) == [["decode call", pytest.approx(12e-9)],
                                 ["harvest", pytest.approx(7e-9)]]


def test_programs_attributed_by_their_kernels():
    s = hand()
    kinds = [p["kind"] for p in T.programs(s["devices"][0])]
    assert kinds == ["prefill", "decode", "other"]
    assert T.program_time(s, "decode") == (pytest.approx(40e-9), 1)
    assert T.program_time(s, "prefill") == (pytest.approx(40e-9), 1)
    assert T.op_time(s, "decode", T.DECODE_KERNELS) == pytest.approx(20e-9)
    assert T.op_time(s, "decode", ("all-reduce",)) == pytest.approx(4e-9)
    assert T.op_time(s, "prefill", T.PREFILL_KERNELS) == \
        pytest.approx(20e-9)
    # containers are left out of the top operations
    assert [n for n, _ in T.top_ops(s)][:2] == [
        "flash_prefill_attention", "paged_decode_attention"]


def test_label_prefers_innermost_span():
    spans = hand()["spans"]
    assert T.label_at(spans, 10) == "admit/prefill"
    assert T.label_at(spans, 47) == "decode call"
    assert T.label_at(spans, 99) == "harvest"
    assert T.label_at(spans, 150) == "other"


def test_recorded_trace():
    s = T.read(FIXTURE)
    busy, window = T.busy_share(s)
    assert 0 < busy < window
    secs, n = T.program_time(s, "decode")
    assert n == 2 and 0.4 < secs < 0.5                 # 0.22 s a step
    kern = T.op_time(s, "decode", T.DECODE_KERNELS)
    assert 0.6 < kern / secs < 0.8                     # the kernel's share
    top = T.top_ops(s, 3)
    assert top[0][0] == "paged_decode_attention"
    assert sum(v for _, v in T.idle_by_label(s).items()) == \
        pytest.approx(window - busy)
    assert T.idle_gaps(s, 1)[0][0] == "harvest"


def test_save_read_roundtrip(tmp_path):
    s = hand()
    p = str(tmp_path / "t.json.gz")
    T.save(s, p)
    assert T.read(p) == s
