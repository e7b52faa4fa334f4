"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  ``--full`` restores paper-scale
sizes (hours on this CPU container; default sizes finish in minutes).
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None, help="substring filter on bench name")
    ap.add_argument(
        "--out-dir",
        default=None,
        help="directory for BENCH_*.json artifacts (sets BENCH_OUT_DIR; "
        "without it no JSON is written unless BENCH_JSON names a file)",
    )
    args = ap.parse_args()
    if args.out_dir:
        os.environ["BENCH_OUT_DIR"] = args.out_dir

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from . import (
        bench_cluster,
        bench_durability,
        bench_graph_scaling,
        bench_grouped,
        bench_join,
        bench_obs,
        bench_offline,
        bench_online_batch,
        bench_params,
        bench_pruning,
        bench_query_scaling,
        bench_serving,
        bench_stacked,
        bench_standing,
        bench_updates,
        bench_vs_baselines,
    )

    benches = [
        ("online_batch", bench_online_batch.run),
        ("grouped", bench_grouped.run),
        ("stacked", bench_stacked.run),
        ("updates", bench_updates.run),
        ("serving", bench_serving.run),
        ("standing", bench_standing.run),
        ("cluster", bench_cluster.run),
        ("join", bench_join.run),
        ("obs", bench_obs.run),
        ("durability", bench_durability.run),
        ("fig8_pruning", bench_pruning.run),
        ("fig9_baselines", bench_vs_baselines.run),
        ("fig7_params", bench_params.run),
        ("fig10_11_query", bench_query_scaling.run),
        ("fig12_graph", bench_graph_scaling.run),
        ("fig5_13_offline", bench_offline.run),
    ]
    print("name,us_per_call,derived")
    t0 = time.perf_counter()
    for name, fn in benches:
        if args.only and args.only not in name:
            continue
        try:
            fn(full=args.full)
        except Exception as e:  # keep the harness running
            print(f"{name},nan,ERROR:{type(e).__name__}:{e}", file=sys.stdout)
            raise
    print(f"# total_wall_s={time.perf_counter()-t0:.1f}", file=sys.stderr)


if __name__ == "__main__":
    main()
