"""Observability overhead: instrumented vs ``obs.disable()`` serving.

The obs layer's contract is that it is cheap enough to leave on in
production: module-flag-guarded counters, one small lock per metric
child, and spans only materialised for *sampled* traces.
This bench proves it on a bench_serving-style stream — a
``MatchServer`` tick loop draining query batches, with one update
epoch landing between measured passes — over identical engine replicas
(same graph, same seed, same update stream), three arms per repeat:

* **off** — ``obs.disable()``: the baseline;
* **sampled** — metrics on, ``trace_rate=0.25`` (the production
  shape: every request counted, a quarter fully traced) — THE GATED
  ARM (``overhead_under_5pct``);
* **full** — metrics on, ``trace_rate=1.0``: every tick traced.
  Reported ungated
  (``overhead_pct_full_trace``) — it is the knowingly-paid debug mode
  and documents exactly what sampling buys.

Arms interleave inside each repeat so drift hits all three equally;
each update epoch re-warms every arm off the clock (fresh delta shapes
compile new probe variants, and a compile is not instrumentation
overhead); and the reported overheads are *median* per-repeat ratios —
robust to one noisy pass on a shared CPU container.  CI gates
``overhead_under_5pct`` plus ``export_parse_ok`` (the post-run registry
snapshot survives the Prometheus round trip with a consistent funnel)
via benchmarks/compare.py; wall times stay unbanded because the ratio,
not the absolute, is the contract.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

from repro.core import GraphUpdate
from repro.obs import TRACER, disable, enable, parse_prometheus, to_prometheus, trace_query
from repro.obs.metrics import REGISTRY
from repro.serve.match_server import MatchServeConfig, MatchServer

from .common import artifact_path, build_engine, emit, make_graph, sample_queries

ROUNDS = 10  # ticks per measured pass
BATCH = 8
REPEATS = 5  # measured passes per arm; one update epoch between each
SAMPLED_RATE = 0.25  # the gated arm's trace sampling


def _updates(rng, g, n):
    out = []
    e = g.edge_array()
    for _ in range(n):
        out.append(
            GraphUpdate(
                remove_edges=e[rng.choice(e.shape[0], size=2, replace=False)],
                add_edges=rng.integers(0, g.n_vertices, size=(2, 2)),
            )
        )
    return out


def _pass(srv, stream, traced: bool) -> float:
    """Drain one query pass through the tick loop; returns wall seconds."""
    t0 = time.perf_counter()
    for r in range(ROUNDS):
        for q in stream[r * BATCH : (r + 1) * BATCH]:
            srv.submit(q)
        if traced:
            with trace_query(f"bench-round-{r}"):
                srv.run_until_drained()
        else:
            srv.run_until_drained()
    return time.perf_counter() - t0


def _advance(srv, update, stream, traced: bool) -> None:
    """Unmeasured epoch advance: apply one update, then re-warm the
    query pass at the new engine state (fresh delta shapes compile new
    probe variants — in EVERY arm — and compiles must not be billed to
    the instrumentation)."""
    srv.submit_update(update)
    srv.run_until_drained()
    _pass(srv, stream, traced)


def run(full: bool = False, json_path: str | None = None) -> dict:
    n = 10_000 if full else 4_000
    g = make_graph(n=n, seed=13)
    # identical replicas so the same update stream replays in every arm
    # and every interleaved repeat compares like engine state with like
    engines = {
        arm: build_engine(g, partition_size=250, index_kind="grouped", group_size=16)
        for arm in ("off", "sampled", "full")
    }
    servers = {
        arm: MatchServer(eng, MatchServeConfig(max_batch=BATCH, schedule="cost"))
        for arm, eng in engines.items()
    }
    pool = sample_queries(g, n=8, seed0=77)
    rng = np.random.default_rng(0)
    stream = [pool[int(rng.integers(0, len(pool)))] for _ in range(ROUNDS * BATCH)]
    updates = {arm: _updates(np.random.default_rng(3), g, REPEATS) for arm in servers}

    def _arm(arm):
        """Set obs state for one arm; returns whether passes trace."""
        if arm == "off":
            disable()
            return False
        enable()
        TRACER.trace_rate = SAMPLED_RATE if arm == "sampled" else 1.0
        return True

    walls = {arm: [] for arm in servers}
    old_rate = TRACER.trace_rate
    try:
        # warm every replica (JIT compile + first-touch) outside the
        # clock, each in the mode it will be measured in
        for arm, srv in servers.items():
            traced = _arm(arm)
            _pass(srv, stream, traced)
        for rep in range(REPEATS):
            for arm, srv in servers.items():
                traced = _arm(arm)
                # one update epoch lands between measured passes (same
                # stream in every arm), keeping the workload mixed
                # without billing fresh-shape compiles to any arm
                _advance(srv, updates[arm][rep], stream, traced)
                walls[arm].append(_pass(srv, stream, traced))
    finally:
        enable()
        TRACER.trace_rate = old_rate

    def _overhead(arm):
        ratios = [a / b for a, b in zip(walls[arm], walls["off"])]
        return 100.0 * (float(np.median(ratios)) - 1.0)

    overhead_pct = _overhead("sampled")
    overhead_full = _overhead("full")
    under_5 = bool(overhead_pct <= 5.0)

    # the instrumented arms must also leave a coherent export behind:
    # parseable Prometheus text whose funnel ordering holds
    parsed = parse_prometheus(to_prometheus(REGISTRY.snapshot()))
    leaf = parsed.get('gnnpe_funnel_total{stage="leaf_pairs"}', 0.0)
    cand = parsed.get('gnnpe_funnel_total{stage="candidates"}', 0.0)
    matches = parsed.get('gnnpe_funnel_total{stage="matches"}', 0.0)
    ticks = parsed.get("gnnpe_server_tick_seconds_count", 0.0)
    export_ok = bool(ticks > 0 and leaf >= cand >= matches > 0)
    pruning = 1.0 - cand / leaf if leaf else 0.0

    mean = lambda arm: sum(walls[arm]) / len(walls[arm])  # noqa: E731
    emit(
        "obs/sampled",
        1e6 * mean("sampled"),
        f"rounds={ROUNDS} batch={BATCH} rate={SAMPLED_RATE} "
        f"overhead={overhead_pct:+.2f}% under5={under_5}",
    )
    emit(
        "obs/full_trace",
        1e6 * mean("full"),
        f"rate=1.0 overhead={overhead_full:+.2f}%",
    )
    emit(
        "obs/disabled",
        1e6 * mean("off"),
        f"export_ok={export_ok} pruning={pruning:.3f}",
    )

    rec = {
        "n_vertices": int(g.n_vertices),
        "rounds": ROUNDS,
        "batch": BATCH,
        "repeats": REPEATS,
        "sampled_trace_rate": SAMPLED_RATE,
        "sampled_wall_s": mean("sampled"),
        "full_trace_wall_s": mean("full"),
        "disabled_wall_s": mean("off"),
        "overhead_pct": overhead_pct,
        "overhead_pct_full_trace": overhead_full,
        "overhead_under_5pct": under_5,
        "export_parse_ok": export_ok,
        "funnel_pruning_power": pruning,
        "n_traces_ringed": len(TRACER.recent()),
    }
    json_path = artifact_path("BENCH_obs.json", json_path)
    if json_path:
        with open(json_path, "w") as f:
            json.dump(rec, f, indent=1)
    return rec


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    print("name,us_per_call,derived")
    rec = run(full=args.full, json_path=args.json)
    print(
        f"# obs overhead {rec['overhead_pct']:+.2f}% at trace_rate="
        f"{rec['sampled_trace_rate']} ({rec['overhead_pct_full_trace']:+.2f}% "
        f"at 1.0); export_parse_ok={rec['export_parse_ok']}"
    )
