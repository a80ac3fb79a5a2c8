"""Benchmark harness — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--fast] [--skip-roofline] \
        [--only SECTION] [--json OUT_DIR]

Prints ``name,us_per_call,derived`` CSV; with ``--json`` also writes the
machine-readable ``BENCH_quant.json`` / ``BENCH_serving.json`` reports
(benchmarks/report.py schema) that CI uploads as artifacts and
``scripts/compare_bench.py`` diffs against a baseline. ``--only`` limits
the run to one section (``quant`` / ``serving`` / ``fleet`` / ``kernels``)
— the sharded CI lane uses ``--only serving`` so the multi-device process
doesn't redo the whole suite.
  quant_fig6a_*    paper Fig 6a (average inference time, 3 variants)
  quant_fig6b_*    paper Fig 6b (latency distribution)
  quant_size_*     paper text: ~4x size reduction
  quant_accuracy_* paper text: small accuracy degradation
  lifecycle_*      paper §4 lifecycle operations
  serving_cb_*     continuous-batching v2 engine under seeded open-loop load
  fleet_*          Fleet v2 event-driven simulator: rollout convergence,
                   per-variant fleet latency, rollback MTTR (virtual-time)
  roofline_*       deliverable (g): per (arch x shape x mesh) dry-run terms
"""
import argparse
import sys

SECTIONS = ("quant", "serving", "fleet", "kernels")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--skip-roofline", action="store_true")
    ap.add_argument("--only", choices=SECTIONS, default=None,
                    help="run a single benchmark section")
    ap.add_argument("--json", metavar="OUT_DIR", default=None,
                    help="also write BENCH_*.json reports into OUT_DIR")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    def wanted(section: str) -> bool:
        return args.only is None or args.only == section

    from benchmarks.report import write_report

    print("name,us_per_call,derived")
    payloads = {}
    if wanted("quant"):
        from benchmarks import lifecycle_bench, quant_ablation, quant_bench

        quant_lines, payloads["quant"] = quant_bench.run(
            iters=4 if args.fast else 10)
        for line in quant_lines:
            print(line)
        sys.stdout.flush()
        for line in quant_ablation.run():
            print(line)
        sys.stdout.flush()
        for line in lifecycle_bench.run():
            print(line)
        sys.stdout.flush()
    if wanted("serving"):
        from benchmarks import serving_bench

        serving_lines, payloads["serving"] = serving_bench.run(
            fast=args.fast)
        for line in serving_lines:
            print(line)
        sys.stdout.flush()
    if wanted("fleet"):
        from benchmarks import fleet_bench

        fleet_lines, payloads["fleet"] = fleet_bench.run(fast=args.fast)
        for line in fleet_lines:
            print(line)
        sys.stdout.flush()
    if wanted("kernels"):
        from benchmarks import kernels_bench

        kernel_lines, payloads["kernels"] = kernels_bench.run(
            fast=args.fast)
        for line in kernel_lines:
            print(line)
        sys.stdout.flush()
    if args.json:
        #: payload sections that carry *metrics* (flattened + gated by
        #: scripts/compare_bench.py); everything else is run config
        result_keys = ("variants", "rollout", "shared_prefix", "kv_pressure",
                       "spec_decode", "kv_precision", "sharded", "router")
        for bench, payload in payloads.items():
            results = {k: payload[k] for k in result_keys if k in payload}
            config = {k: v for k, v in payload.items()
                      if k not in result_keys}
            config["fast"] = args.fast
            path = write_report(args.json, bench, results, config)
            print(f"# wrote {path}", file=sys.stderr)
    if not args.skip_roofline and args.only is None:
        from benchmarks import roofline

        for line in roofline.run():
            print(line)


if __name__ == "__main__":
    main()
