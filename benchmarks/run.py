"""Benchmark driver — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.

  applicability   — Tables 1/2 (loop-corpus preconditions)
  tpch_loops      — Figure 9(a) (cursor vs Aggify vs Aggify+)
  app_loops       — Figure 9(b) + §10.6 (client loops, data movement)
  workload_loops  — Figure 9(c)/Table 3 (L1..L8 incl. nested, inserts)
  logical_reads   — Table 4
  scalability     — Figures 10/11/12
  roofline        — §Roofline terms from the dry-run artifacts
  group_agg       — grouped-aggregation mode shoot-out (stream vs
                    recognized vs fused Pallas path; docs/execution-modes.md)
  serve_agg       — aggregate-serving layer: cached vs fresh-jit p50,
                    1k-request concurrent qps, trace/slot-build counters
                    (docs/serving.md)
  ingest          — sustained micro-batch ingest: resident incremental
                    folding vs append+full-refresh recompute
                    (docs/serving.md "Incremental ingest")
"""
from __future__ import annotations

import argparse
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="all",
                    help="comma list of benchmark names")
    ap.add_argument("--scale", type=float, default=0.0005)
    ap.add_argument("--full", action="store_true",
                    help="larger data sizes (slower)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write results as a JSON artifact (the "
                         "committed BENCH_*.json baselines use this)")
    args = ap.parse_args()

    from repro.launch.compile_cache import configure_compile_cache
    configure_compile_cache()
    from . import (app_loops, applicability, group_agg, ingest,
                   logical_reads, roofline_bench, scalability, serve_agg,
                   tpch_loops, workload_loops)

    scale = 0.005 if args.full else args.scale
    sizes = ((100, 1_000, 10_000, 100_000, 1_000_000, 3_000_000)
             if args.full else (100, 1_000, 10_000, 100_000))
    benches = {
        "applicability": lambda: applicability.run(),
        "tpch_loops": lambda: tpch_loops.run(scale=scale),
        "app_loops": lambda: app_loops.run(scale=scale),
        "workload_loops": lambda: workload_loops.run(),
        "logical_reads": lambda: logical_reads.run(scale=scale),
        "scalability": lambda: scalability.run(sizes=sizes),
        "roofline": lambda: roofline_bench.run(),
        "group_agg": lambda: group_agg.run(
            n=200_000 if args.full else 50_000),
        # serving measures per-call overheads (trace / slot / launch),
        # not row throughput — group_agg owns the big-n axis
        "serve_agg": lambda: serve_agg.run(
            n=50_000 if args.full else 8_192),
        # whole-plan fusion acceptance: fused vs materialized
        # filter-join-agg chain at 100× the default loop scale factor
        "tpch_join": lambda: tpch_loops.run_join_agg(),
        # sustained-ingest acceptance: resident O(batch) folds vs the
        # append+O(table)-refresh model on an identical batch stream
        "ingest": lambda: ingest.run(
            n=200_000 if args.full else 50_000),
    }
    only = None if args.only == "all" else set(args.only.split(","))
    print("name,us_per_call,derived")
    failures = 0
    from .util import reset_results, write_json
    reset_results()
    for name, fn in benches.items():
        if only and name not in only:
            continue
        try:
            fn()
        except Exception as e:  # keep the harness running; report at exit
            import traceback
            traceback.print_exc()
            print(f"{name},0,ERROR:{type(e).__name__}")
            failures += 1
    if args.json:
        write_json(args.json)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
