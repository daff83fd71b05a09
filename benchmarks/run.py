"""Benchmark runner — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  ``--full`` runs the complete
paper grids (d up to 100 etc.); the default profile keeps CI runtime modest.
``--json [PATH]`` additionally writes every recorded row (with structured
metrics such as speedups) to PATH — default ``BENCH_kernels.json``.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full paper grids (slow: d up to 100)")
    ap.add_argument("--only", default=None, help="substring filter on module")
    ap.add_argument("--json", nargs="?", const="BENCH_kernels.json",
                    default=None, metavar="PATH",
                    help="write structured results (default BENCH_kernels.json)")
    args, _ = ap.parse_known_args()

    from repro.runtime import enable_compile_cache
    enable_compile_cache()

    from . import (table2_3_marginals_scaling, table4_5_accuracy,
                   table6_9_rplus, table10_14_crossover, fig1_3_fairness,
                   discrete_overhead, discrete_bench, kernels_bench,
                   kernels_autotune_bench, planner_bench, release_bench,
                   roofline_bench, serve_bench)
    modules = [table2_3_marginals_scaling, table4_5_accuracy, table6_9_rplus,
               table10_14_crossover, fig1_3_fairness, discrete_overhead,
               discrete_bench, kernels_bench, kernels_autotune_bench,
               planner_bench, release_bench, roofline_bench, serve_bench]
    print("name,us_per_call,derived")
    failed = 0
    for mod in modules:
        if args.only and args.only not in mod.__name__:
            continue
        try:
            mod.run(fast=not args.full)
        except Exception:  # noqa: BLE001
            failed += 1
            print(f"{mod.__name__},nan,EXCEPTION", file=sys.stderr)
            traceback.print_exc()
    if args.json:
        from .common import JSON_ROWS
        with open(args.json, "w") as fh:
            json.dump({"profile": "full" if args.full else "fast",
                       "rows": JSON_ROWS}, fh, indent=2)
        print(f"wrote {len(JSON_ROWS)} rows to {args.json}", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
