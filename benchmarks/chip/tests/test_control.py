"""The control at a size a test run holds: the reference computed in fp8
(the step below the bf16 the configuration states), put in the program's
place, reads a widest gap that a limit set between the two readings
fails, while the program's own run passes it. On the chip, at the cells'
own sizes, ``control.py`` takes the readings that ``cells/<cell>.json``
records."""
import math
import os
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, CHIP)
sys.path.insert(0, os.path.join(CHIP, "tests"))

import harness  # noqa: E402
from tiny_cell import tiny_cell  # noqa: E402


def test_fp8_control_fails_where_the_program_passes():
    cell = tiny_cell()
    cell["mix"]["generator"]["output"].update(median=10, max=12)
    out = harness.run(cell, 2**32 + 9, 4.0, False, t_start=harness.clock(),
                      require_tpu=False, backend="ref", cache=False,
                      controls=("fp8",), log=lambda _m: None)
    lower = out["check"]["widest_gap"]["value"]
    upper = out["control_gap"]["fp8"]["widest_gap"]
    assert out["correct"] is True
    assert upper >= 3 * max(lower, 1e-3)
    limit = math.sqrt(max(lower, 1e-3) * upper)   # between, as on the chip
    assert lower <= limit < upper
    # the run judges the control by the cell's limits as it judges the
    # program; under the limit set between the readings it is not correct
    assert out["control_correct"] == {"fp8": harness.judged(
        out["control_gap"]["fp8"], cell["own"]["limits"])}
    assert harness.judged(out["gaps"], {"widest_gap": limit})
    assert not harness.judged(out["control_gap"]["fp8"],
                              {"widest_gap": limit})


def test_program_int8_variant_serves_as_the_control():
    """The program's own dynamic int8 weights, switched on in place of the
    bf16 ones, are served through the same run and read by both gaps (at
    this size int8 departs from bf16 by about as little as bf16 does from
    float32: the chip's readings at the cells' sizes are in PERF.md)."""
    cell = tiny_cell()
    seen = []
    kw = dict(t_start=harness.clock(), require_tpu=False, backend="ref",
              cache=False)
    sound = harness.run(cell, 2**32 + 24, 3.0, False, log=lambda _m: None,
                        **kw)
    ctl = harness.run(cell, 2**32 + 24, 3.0, False, variant="dynamic_int8",
                      log=seen.append, **kw)
    assert "weights: serving the program's dynamic_int8 variant" in seen
    assert set(ctl["gaps"]) == {"widest_gap", "mean_gap"}
    assert ctl["gaps"] != sound["gaps"]
    assert ctl["gaps"]["widest_gap"] >= ctl["gaps"]["mean_gap"] > 0


@pytest.mark.parametrize("variant,control_ok,want", [
    ("", False, 0), ("", True, 1),
    ("dynamic_int8", False, 0), ("dynamic_int8", True, 1)])
def test_control_command_fails_where_a_control_comes_out_correct(
        monkeypatch, variant, control_ok, want):
    """``control.py`` exits 1 where a control passes the cell's limits: a
    lower precision of the reference by its ``control_correct``, the
    program's own variant by the run's ``correct``."""
    # the command names its compilation cache as it is imported; the
    # environment is given back after the test
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    import control

    def fake_run(cell, seed, *_a, controls=(), variant=None, **_k):
        gaps = {"widest_gap": 0.01, "mean_gap": 0.001}
        out = {"correct": control_ok if variant else True, "attempted": 3,
               "failed": 0, "gaps": gaps,
               "check": {"short_requests": {"value": 0, "limit": 0}}}
        if controls:
            out["control_gap"] = {q: gaps for q in controls}
            out["control_correct"] = {q: control_ok for q in controls}
        return out

    monkeypatch.setattr(control.H, "run", fake_run)
    monkeypatch.setattr(control.H, "load_cell", lambda _n: {})
    args = ["--workload", "x", "--seeds", "1,2", "--controls",
            "" if variant else "int8", "--variant", variant]
    assert control.main(args) == want
