#!/usr/bin/env python3
"""Find a cell's knee once, on the chip: offer its mix at several fixed
rates, in increasing order on one engine, and report for each the TTFT
tail, the backlog at the window's opening and close, the output tokens per
second completed against those offered, and how full the KV pool and the
slots ran.

    python3 benchmarks/chip/sweep.py --workload stablelm-1.6b.chat \\
        --rates 0.6,0.8,1.0 --seconds 40 --seed 11 [--traffic <mix>]

The knee is the last rate, going up, whose TTFT p90 stays within
``TTFT_FACTOR`` times the lowest rate's and whose backlog grows by at most
``BACKLOG`` requests over the window. (Completed over offered tokens
swings too much between neighbouring rates when requests last most of the
window.) The cell then runs at a fixed share of it, written into
``cells/<cell>.json``. ``--traffic`` sweeps another mix file in the cell's
place. This is a tool for the PR that defines a cell; the benchmark's runs
do not call it.
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
import traffic as TR     # noqa: E402

#: the knee's TTFT p90 at most this many times the lowest rate's
TTFT_FACTOR = 1.5
#: the knee's backlog grows by at most this many requests over the window
BACKLOG = 3


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def reset(engine) -> None:
    """Withdraw whatever is left, so the next rate starts empty."""
    for r in list(engine.all_requests):
        engine.cancel(r)
    engine.run()
    engine.all_requests.clear()
    engine.kv.reset()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--seed", type=int, default=11)
    ap.add_argument("--traffic", default="")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    cell = H.load_cell(args.workload)
    if args.traffic:
        with open(os.path.join(HERE, "traffic", args.traffic + ".json")) as f:
            cell["mix"] = json.load(f)
    if jax.devices()[0].platform != "tpu" or len(jax.devices()) < \
            cell["chips"]:
        log("sweep.py: needs the cell's TPU chips")
        return 3
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    mix = cell["mix"]
    cfg, engine = H.build(cell, args.seed, None, log)
    H.warm(engine, mix, log)
    rows = []
    for rate in sorted(float(r) for r in args.rates.split(",")):
        reqs = TR.generate(mix["generator"], rate=rate, seed=args.seed,
                           vocab=cfg.vocab_size, warm_s=mix["warm_in_s"],
                           window_s=args.seconds)
        res = H.drive(engine, reqs, seconds=args.seconds,
                      warm_s=mix["warm_in_s"], drain_s=0.0, log=log)
        e2e = H.end_to_end(reqs, res, args.seconds)
        due = [k for k, r in enumerate(reqs) if 0 <= r.due < args.seconds]
        offered = sum(reqs[k].max_new_tokens for k in due) / args.seconds
        t0, tc = res["t0"], res["t_close"]
        # requests due by a time and not yet given their first token then
        backlog = [sum(1 for k, r in enumerate(reqs)
                       if res["t0"] + r.due <= t and not (
                           res["tok_times"][k] and res["tok_times"][k][0] <= t))
                   for t in (t0, tc)]
        row = {"rate": rate, "offered_tok_s": offered,
               "completed_tok_s": e2e["output_tok_s"],
               "ratio": e2e["output_tok_s"] / offered if offered else 0.0,
               "backlog_open": backlog[0], "backlog_close": backlog[1],
               "ttft_p90_s": e2e["ttft_p90_s"],
               "itl_p95_ms": e2e["itl_p95_ms"],
               "decode_step_ms": e2e["decode_step_ms"],
               "kv_peak_blocks": res["snap"]["kv_peak"],
               "kv_blocks": engine.kv.alloc.usable_blocks,
               "slots_mean": (sum(res["live"]) / len(res["live"])
                              if res["live"] else 0.0),
               "attempted": e2e["attempted"]}
        rows.append(row)
        log(json.dumps(row))
        reset(engine)
    knee = None                 # the last rate before the first that fails
    for r in rows:
        if (r["ttft_p90_s"] > TTFT_FACTOR * rows[0]["ttft_p90_s"]
                or r["backlog_close"] - r["backlog_open"] > BACKLOG):
            break
        knee = r["rate"]
    print(json.dumps({"workload": args.workload,
                      "traffic": args.traffic or None, "knee": knee,
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
