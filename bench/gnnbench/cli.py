"""Command line of the benchmark: one run of one cell, one JSON line last.

    python3 bench/run.py --workload nws100k.read --seed 7 --seconds 30 --trace 0

Runs on the machine it is started on and needs its accelerator: without a
TPU, with fewer chips than the cell asks for, or on a device kind missing
from ``bench/peaks.json`` it exits non-zero before building anything, and
so it does for a cell with no measured rate in ``bench/cells/``.
``--sweep r1,r2,...`` repeats the window at each offered rate after one
set-up and logs a line per rate (how a cell's rate is found); it prints
no result line.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys

from . import cell as cellmod


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def check_devices(chips: int, peaks: dict) -> str | None:
    """Why this machine cannot run the cell, or None."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return f"no TPU: JAX runs on {devices[0].platform!r}; this benchmark measures the chip"
    if len(devices) < chips:
        return f"the cell needs {chips} chip(s), JAX sees {len(devices)}"
    if devices[0].device_kind not in peaks:
        return f"device kind {devices[0].device_kind!r} is not in bench/peaks.json"
    return None


def enable_compile_cache() -> str:
    import jax

    path = str(cellmod.CACHE_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path  # the one the program would take too
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def metrics_of(run, metrics: list) -> dict:
    out = {}
    for m in metrics:
        value = cellmod.reader(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def honesty_lines(run) -> list[str]:
    """Set-up split and how late the generator ran, for earlier lines."""
    p = run.setup_parts
    late = cellmod.percentile(run.lateness_s, 95) or 0.0
    n_upd = len(run.update_due)
    lat = run.match_latencies_s()
    tail = ", ".join(
        f"p{q} {cellmod.percentile(lat, q) * 1e3:.3f} ms" for q in (50, 90, 95, 99) if lat
    )
    return [
        f"bench: match latency over {len(lat)} requests: {tail}",
        "bench: setup_s split: " + ", ".join(
            f"{k} {v:.3f}" if isinstance(v, float) else f"{k} {v}" for k, v in p.items()
        ) + f"; reference (after the window, not in setup_s) {run.reference_s:.3f} s",
        f"bench: generator p95 lateness {late * 1e3:.3f} ms (send time minus due time); "
        f"window held {len(run.requests)} match requests and {n_upd} updates; "
        f"queue {run.queue_at_open} at the window's start and {run.queue_at_close} at its "
        f"close; answers drained {run.drained_at - run.window[1]:.3f} s after close",
    ]


def main(t_process0: float, argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None, help="comma-separated offered rates (op/s)")
    args = ap.parse_args(argv)

    cell = cellmod.resolve(cellmod.load_spec(), args.workload)
    why_not = check_devices(cell.chips, cellmod.load_peaks())
    if why_not is None and cell.rate is None and args.sweep is None:
        why_not = f"no bench/cells/{args.workload}.json: measure the cell's rate with --sweep"
    if why_not is not None:
        log(f"bench: {why_not}")
        return 2
    log(f"bench: compile cache {enable_compile_cache()}")
    trace_dir = str(cellmod.ROOT / ".bench_trace" / args.workload)
    shutil.rmtree(trace_dir, ignore_errors=True)
    sweep = [float(r) for r in args.sweep.split(",")] if args.sweep else None
    run, checks = cellmod.run_cell(
        cell, args.seed, args.seconds, bool(args.trace), t_process0, log=log,
        trace_dir=trace_dir, sweep=sweep,
    )
    shutil.rmtree(trace_dir, ignore_errors=True)
    for line in honesty_lines(run):
        log(line)
    if sweep is not None:
        return 0
    metrics = metrics_of(run, cell.per_layer if args.trace else cell.end_to_end)
    failed = sum(r.response is None or r.response.status != "ok" for r in run.requests)
    failed += sum(1 for v in run.update_visible_s() if v == float("inf"))
    result = {
        "correct": cellmod.correct(checks),
        "attempted": len(run.requests) + len(run.update_due),
        "failed": failed,
        "metrics": metrics,
        "device": dict(run.device),
    }
    if args.trace and run.trace is not None:
        result["device"]["busy_s"] = run.trace["busy_s"]
        result["device"]["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace["device_ops"],
            "idle_gaps": run.trace["idle_gaps"],
        }
    result["checks"] = checks
    if run.lateness_s:
        log(f"bench: generator mean lateness {statistics.fmean(run.lateness_s) * 1e3:.3f} ms")
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0
