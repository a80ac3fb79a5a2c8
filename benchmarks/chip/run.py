#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload stablelm-1.6b.chat \\
        --seed 7 --seconds 51 --trace 0

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` takes a
profiler trace of the last seconds of the window and reports its
per-layer metrics. Progress goes to standard error, ending with the numbers
the correctness check compared, each beside its limit. The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``check``. Without a TPU, or with fewer chips than the cell asks for, it
exits 3 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

# repro: allow-wallclock -- set-up time runs from process start
T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
# JAX's persistent compilation cache lives in the checkout, at a fixed path
# (the program's own default), whatever the environment names: a cache
# shared with another checkout would let one side's compiles serve the other
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    os.path.dirname(os.path.dirname(HERE)), ".jax_cache")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness

    cell = harness.load_cell(args.workload)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START, log=log)
    except harness.NoChip as e:
        log(f"run.py: {e}")
        return 3
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
