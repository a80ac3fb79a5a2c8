"""The control at a size a test run holds: the reference computed in fp8
(the step below the bf16 the configuration states), put in the program's
place, reads a widest gap that a limit set between the two readings
fails, while the program's own run passes it. On the chip, at the cells'
own sizes, ``control.py`` takes the readings that ``cells/<cell>.json``
records."""
import math
import os
import sys

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
