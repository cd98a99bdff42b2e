"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--fast`` trims sweeps.
Mapping to the paper:
  bench_rd                  -> Fig. 8 (RD curves) + Fig. 9 (Pareto)
  bench_throughput          -> Fig. 12 (PRD-binned) + Table 3 (stability)
  bench_stage_breakdown     -> Fig. 13 (kernel runtime split)
  bench_ne_sweep            -> Fig. 14 (N x E throughput surface)
  bench_params              -> Table 1 + Table 2
  bench_compression_integration -> beyond-paper: grad/ckpt compression
  bench_roofline            -> EXPERIMENTS.md §Roofline (from dry-run)
  bench_serving             -> beyond-paper: front-end p50/p99 vs load + knee
"""
from __future__ import annotations

import argparse
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names (e.g. rd,roofline)")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        bench_compression_integration,
        bench_ne_sweep,
        bench_params,
        bench_rd,
        bench_reconstruction,
        bench_roofline,
        bench_serving,
        bench_stage_breakdown,
        bench_throughput,
    )

    suite = {
        "params": bench_params.run,
        "rd": bench_rd.run,
        "throughput": bench_throughput.run,
        "serving": bench_serving.run,
        "stage_breakdown": bench_stage_breakdown.run,
        "ne_sweep": bench_ne_sweep.run,
        "reconstruction": bench_reconstruction.run,
        "integration": bench_compression_integration.run,
        "roofline": bench_roofline.run,
    }
    only = set(args.only.split(",")) if args.only else None
    print("name,us_per_call,derived")
    failures = []
    for name, fn in suite.items():
        if only and name not in only:
            continue
        try:
            fn(fast=args.fast)
        except Exception:
            traceback.print_exc()
            failures.append(name)
    if failures:
        print(f"FAILED benches: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
