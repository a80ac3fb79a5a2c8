#!/usr/bin/env python3
"""Readings that the correctness limit of a cell is set from, on the chip.

For each seed, one run of the cell as the benchmark makes it (a short
window at the cell's own rate, drained so the longest requests finish),
then the reference over the checked requests: the program's widest and mean
gaps, and, on the first ``--controls-on`` seeds, the gaps of the first
choices of the reference computed in each lower precision (``--controls``).
With ``--variant``, the program serves that weight variant of its own
(``dynamic_int8``): the control where the program has the lower precision
(not at tp > 1, where the program refuses quantized weights; there the
reference's own lower precisions are the control).

    python3 benchmarks/chip/control.py --workload stablelm-1.6b.chat \\
        --seeds 101,102,103 --seconds 15 --controls fp8,int8
    python3 benchmarks/chip/control.py --workload stablelm-1.6b.chat \\
        --seeds 104,105,106 --seconds 15 --controls "" --variant dynamic_int8

The lower reading of a number is its largest over the program's seeds, the
upper its smallest over a control's; ``PERF.md`` gives both and the limit
set between them in ``cells/<cell>.json``. Each control is judged by those
limits as the program is (a ``--variant`` run by its own ``correct``), and
the command exits 1 where any control comes out correct. The benchmark's
runs do not run the controls.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
# the compilation cache in the checkout, as run.py keeps it
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
    os.path.dirname(os.path.dirname(HERE)), ".jax_cache")

import harness as H      # noqa: E402


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15)
    ap.add_argument("--controls", default="fp8,int8")
    ap.add_argument("--controls-on", type=int, default=3)
    ap.add_argument("--variant", default="")
    args = ap.parse_args(argv)

    cell = H.load_cell(args.workload)
    controls = tuple(c for c in args.controls.split(",") if c)
    rows = []
    for j, seed in enumerate(int(s) for s in args.seeds.split(",")):
        try:
            out = H.run(cell, seed, args.seconds, False, t_start=H.clock(),
                        controls=controls if j < args.controls_on else (),
                        variant=args.variant or None, log=log)
        except H.NoChip as e:
            log(f"control.py: {e}")
            return 3
        log("control run " + json.dumps(out))
        judged = dict(out.get("control_correct", {}))
        if args.variant:
            judged[args.variant] = out["correct"]
        row = {"seed": seed, "variant": args.variant,
               "program": out["gaps"], "control": out.get("control_gap", {}),
               "control_correct": judged,
               "short": out["check"]["short_requests"]["value"],
               "attempted": out["attempted"], "failed": out["failed"]}
        rows.append(row)
        log("control row " + json.dumps(row))
    nums = ("widest_gap", "mean_gap")
    most = {k: max(r["program"][k] for r in rows) for k in nums}
    least = {q: {k: min(r["control"][q][k] for r in rows
                        if q in r["control"]) for k in nums}
             for q in controls if any(q in r["control"] for r in rows)}
    passed = sorted({f"{q}@{r['seed']}" for r in rows
                     for q, ok in r["control_correct"].items() if ok})
    print(json.dumps({"workload": args.workload, "variant": args.variant,
                      "program_max": most, "control_min": least,
                      "control_passed": passed, "rows": rows}))
    if passed:
        log(f"control.py: controls that came out correct: {passed}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
