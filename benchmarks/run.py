"""Benchmark entry point — one bench per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV lines (stdout) and writes detailed
CSVs under ``experiments/``.

  fig11  — SNR vs word length (paper Fig. 11)
  fig10  — generator scalability (paper Fig. 10)
  table1 — generator API units (paper Table I)
  fig3   — j-step Φ pipelining (paper Fig. 3)
  fig5   — C-slow retiming (paper Fig. 5)
  lstm   — recurrent-cell throughput (unroll/C-slow sweeps + fused kernel)
  codegen— generated-vs-handwritten-vs-XLA kernel throughput (PR 2)
  kernels— kernel reference micro-benches
  int8   — weight-only int8 serving comparison
  roofline — §Roofline terms from the dry-run artifacts

Suites bundle benches into a single JSON artifact:

  --suite perf [--smoke] — decode sync structure (per-token vs persistent
  K-step), C-slow fused-vs-vmap, int8-vs-fp32 gate path →
  ``benchmarks/BENCH_perf.json`` (the CI perf-trajectory artifact).

  --suite tune [--smoke] — the Fig. 10 auto-tuner loop on the paper's case
  studies → ``benchmarks/BENCH_tune.json`` (repro.tune/v1 Pareto reports,
  validated in CI by ``python -m repro.obs.check``).
"""

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset: fig11 fig10 table1 fig3 fig5 lstm codegen "
                         "kernels int8 roofline perf")
    ap.add_argument("--suite", choices=["perf", "tune"], default=None,
                    help="run one aggregated suite instead of the figure benches")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (CI-sized artifact in seconds)")
    ap.add_argument("--check", action="store_true",
                    help="perf suite only: compare against the committed "
                         "benchmarks/BENCH_perf.json and exit 1 on regression")
    ap.add_argument("--out", default="experiments")
    args = ap.parse_args()

    from repro.launch import compile_cache

    compile_cache.enable()

    from . import (codegen_bench, fig3_jstep, fig5_cslow, fig10_generator,
                   fig11_snr, int8_serving, kernels_bench, lstm_throughput,
                   perf_suite, roofline, table1_api, tune_suite)

    if args.suite == "perf":
        print("name,us_per_call,derived")
        perf_suite.run(args.out, smoke=args.smoke, check_baseline=args.check)
        return
    if args.suite == "tune":
        print("name,us_per_call,derived")
        tune_suite.run(args.out, smoke=args.smoke)
        return

    benches = {
        "fig11": lambda: fig11_snr.run(args.out),
        "fig10": lambda: fig10_generator.run(args.out),
        "table1": lambda: table1_api.run(args.out),
        "fig3": lambda: fig3_jstep.run(args.out),
        "fig5": lambda: fig5_cslow.run(args.out),
        "lstm": lambda: lstm_throughput.run(args.out),
        "codegen": lambda: codegen_bench.run(args.out),
        "kernels": lambda: kernels_bench.run(args.out),
        "int8": lambda: int8_serving.run(args.out),
        "perf": lambda: perf_suite.run(args.out, smoke=args.smoke),
        "roofline": lambda: roofline.run(args.out),
    }
    selected = args.only or [n for n in benches if n != "perf"]
    print("name,us_per_call,derived")
    for name in selected:
        benches[name]()


if __name__ == "__main__":
    main()
