"""Stacked-tensor sharded probe vs the per-partition loop traversal.

Two measurements, one JSON artifact (``BENCH_stacked.json``):

1. **End-to-end single-host speedup** — one grouped engine, the same
   16-query batch through ``match_many(probe_impl="loop")`` (per-
   partition ``PackedIndex`` traversal) and ``probe_impl="stacked"``
   (one vmapped descent over the dense stacked partition tensors,
   dist/probe.py).  Match sets are asserted byte-identical.

2. **Multi-device scaling curve** — weak scaling of the sharded device
   stage: subprocess workers pin ``XLA_FLAGS=--xla_force_host_platform_
   device_count=D`` for D ∈ {1, 2, 4}, build a synthetic stacked index
   with a FIXED number of partitions per device, and time the
   shard_map'd mask stage.  The curve reports probe throughput
   (partition·query cells/s) plus the deterministic per-shard load from
   the greedy balanced layout.  On this CPU container every virtual
   device shares the host cores, so throughput saturates at the
   physical core count — ``scaling_monotone`` therefore allows a small
   tolerance (each point ≥ 0.85 × the best preceding point); on real
   multi-chip hardware the same harness measures true scaling.

CI gates ``match_sets_identical`` + the speedup via benchmarks/compare.py.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import jax

from .common import artifact_path, build_engine, emit, make_graph, sample_queries

BATCH = 16
GROUP_SIZE = 16
SCALING_DEVICES = (1, 2, 4)
PARTS_PER_DEVICE = 16
SCALING_TOLERANCE = 0.85


def _time_best(fn, repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


# ---------------------------------------------------------------- worker ----


def _scaling_worker(parts_per_device: int) -> dict:
    """Time the sharded device stage on THIS process's device count.

    Synthetic workload (no GNN training): random path embeddings packed
    through the real ``build_index`` + group sidecar, stacked over all
    local devices, probed with a fixed per-device partition count.
    """
    import numpy as np

    from repro.core import build_index
    from repro.core.grouping import attach_groups
    from repro.dist.probe import StackedProbe

    n_dev = len(jax.devices())
    m, P, D, Q = parts_per_device * n_dev, 16384, 6, 128
    rng = np.random.default_rng(0)
    vocab = rng.random((8, 2)).astype(np.float32)
    indexes = []
    for _ in range(m):
        emb = rng.random((P, D)).astype(np.float32)
        lab = rng.integers(0, 8, (P, 3)).astype(np.int32)
        emb0 = vocab[lab].reshape(P, D)
        ix = build_index(
            rng.integers(0, 100, (P, 3)).astype(np.int32), emb, emb0, block_size=128
        )
        attach_groups(ix, GROUP_SIZE)
        indexes.append(ix)
    probe = StackedProbe(indexes)
    st = probe.stacked
    q_emb = (rng.random((m, Q, D)) * 0.9 + 0.1).astype(np.float32)
    q_emb0 = rng.random((m, Q, D)).astype(np.float32)
    q_cat = np.zeros((st.n_slots, Q, D), np.float32)
    q0 = np.zeros((st.n_slots, Q, D), np.float32)
    q_cat[st.slot_of] = q_emb
    q0[st.slot_of] = q_emb0

    def run():
        probe._device_masks(q_cat, q0, 1e-6, True, "jit")

    run()  # compile out of the timed region
    t = _time_best(run, repeats=5)
    per_shard = np.zeros(st.n_shards, np.int64)
    slots_per_shard = st.n_slots // st.n_shards
    for s in range(st.n_shards):
        per_shard[s] = st.n_paths[s * slots_per_shard : (s + 1) * slots_per_shard].sum()
    return {
        "devices": n_dev,
        "n_partitions": m,
        "probe_s": t,
        "throughput_cells_s": m * Q / t,
        "max_shard_paths": int(per_shard.max()),
        "total_paths": int(st.n_paths.sum()),
    }


def _run_scaling(parts_per_device: int) -> list[dict]:
    """Fan the scaling worker over virtual device counts (subprocesses:
    the XLA device count is fixed at backend init)."""
    out = []
    for d in SCALING_DEVICES:
        env = {
            **os.environ,
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={d}",
            "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu"),
            "PYTHONPATH": os.environ.get("PYTHONPATH", "src"),
        }
        proc = subprocess.run(
            [sys.executable, "-m", "benchmarks.bench_stacked",
             "--scaling-worker", str(parts_per_device)],
            capture_output=True, text=True, timeout=900, env=env,
        )
        line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
        try:
            out.append(json.loads(line))
        except json.JSONDecodeError as e:
            raise RuntimeError(
                f"scaling worker (devices={d}) failed: {proc.stdout}\n{proc.stderr[-2000:]}"
            ) from e
    return out


def _monotone(curve: list[dict], tolerance: float = SCALING_TOLERANCE) -> bool:
    best = 0.0
    for rec in curve:
        if rec["throughput_cells_s"] < tolerance * best:
            return False
        best = max(best, rec["throughput_cells_s"])
    return True


def run(full: bool = False, json_path: str | None = None, scaling: bool = True) -> dict:
    n = 50_000 if full else 20_000
    g = make_graph(n=n, seed=11)
    # smaller partitions than bench_online (160 at default scale): the
    # partition axis is exactly what the stacked probe parallelizes
    eng = build_engine(
        g,
        partition_size=312 if full else 125,
        index_kind="grouped",
        group_size=GROUP_SIZE,
        probe_impl="stacked",
    )
    queries = sample_queries(g, n=BATCH, seed0=42)

    # warm up both traversals (jit compiles leave the timed region)
    loop_all = eng.match_many(queries, probe_impl="loop")
    stacked_all = eng.match_many(queries, probe_impl="stacked")
    for qi, (a, b) in enumerate(zip(stacked_all, loop_all)):
        assert a == b, f"query {qi}: stacked/loop match sets differ"

    t_loop = _time_best(lambda: eng.match_many(queries, probe_impl="loop"))
    t_stacked = _time_best(lambda: eng.match_many(queries, probe_impl="stacked"))
    speedup = t_loop / max(t_stacked, 1e-12)

    nq = len(queries)
    emit("stacked/loop_total", 1e6 * t_loop, f"n_queries={nq} parts={len(eng.models)}")
    emit("stacked/stacked_total", 1e6 * t_stacked, f"speedup={speedup:.2f}x")
    emit(
        "stacked/padding_frac",
        eng.offline_stats["stacked_padding_frac"],
        f"{eng.offline_stats['stacked_bytes']/1e6:.1f}MB stacked",
    )

    if scaling and jax.default_backend() == "tpu":
        # the curve is a CPU virtual-device measurement: a process holding
        # the TPU leaves it out rather than print CPU numbers in a device run
        print("# stacked scaling curve skipped: it runs on virtual CPU devices")
        scaling = False
    curve = _run_scaling(PARTS_PER_DEVICE) if scaling else []
    for rec in curve:
        emit(
            f"stacked/scaling_d{rec['devices']}",
            1e6 * rec["probe_s"],
            f"throughput={rec['throughput_cells_s']:.0f}cells/s "
            f"max_shard_paths={rec['max_shard_paths']}",
        )

    rec = {
        "n_vertices": int(g.n_vertices),
        "n_queries": nq,
        "n_partitions": len(eng.models),
        "loop_total_s": t_loop,
        "stacked_total_s": t_stacked,
        "speedup": speedup,
        "match_sets_identical": True,
        "stacked_bytes": int(eng.offline_stats["stacked_bytes"]),
        "stacked_padding_frac": float(eng.offline_stats["stacked_padding_frac"]),
        "scaling": curve,
        "scaling_monotone": _monotone(curve) if curve else None,
        "scaling_tolerance": SCALING_TOLERANCE,
    }
    json_path = artifact_path("BENCH_stacked.json", json_path)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", default=None)
    ap.add_argument("--no-scaling", action="store_true")
    ap.add_argument("--scaling-worker", type=int, default=None,
                    help="internal: run the scaling worker and print one JSON line")
    args = ap.parse_args()
    if args.scaling_worker is not None:
        print(json.dumps(_scaling_worker(args.scaling_worker)))
        sys.exit(0)
    print("name,us_per_call,derived")
    rec = run(full=args.full, json_path=args.json, scaling=not args.no_scaling)
    print(
        f"# stacked speedup over loop probe: {rec['speedup']:.2f}x; "
        f"scaling monotone: {rec['scaling_monotone']}"
    )
