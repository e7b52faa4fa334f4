"""Chip smoke: serve exact subgraph matches on a TPU through the normal path.

    python3 chip_smoke.py                 # one chip: passes (a) and (b)
    python3 chip_smoke.py --chips 4       # four chips: pass (b) sharded, + reference

Builds a DBLP-shaped data graph (SNAP com-DBLP: 317,080 vertices, average
degree 6.6; 15 labels as in the subgraph-matching literature) as a seeded
Newman–Watts–Strogatz graph, indexes it with ``GnnPeEngine`` (monotone
encoder, grouped index, l=2, ~1,000 vertices per partition), and serves a
seeded request stream through ``MatchService`` twice:

  (a) the stacked probe's host leaf stage, the NumPy join, and the Pallas
      leaf scan the engine picks by itself on a TPU;
  (b) the device-resident path — the stacked probe's device leaf stage,
      device join.

Each pass serves 32 queries (5, 6 and 8 vertices), one update epoch (a few
edge inserts and deletes, which puts the delta buffer's probe on the path)
and 8 more queries.  Every response must be ``ok`` and every match set must
equal the engine's NumPy reference (NumPy leaf scan, NumPy join) at the same
epoch.  That reference shares the stacked probe's group and MBR descent
with both passes, so four queries of each phase, spread over its sizes, are
also held to ``vf2_match`` on the graph at that epoch, which shares no code
with the engine (about 1.6 s a query on the host).  The last line of standard output is one JSON object with the
device JAX reports; it is printed only when every check passed.  Without a
TPU the script exits non-zero before building anything.

``--chips 4`` runs pass (b) alone, with the probe's ``("part",)`` and the
join's ``("join",)`` meshes over four chips.  ``--vertices`` and
``--queries`` shorten a run; the device join compiles a few programs per
new query shape, so the query count sets most of a cold run's time (each
phase prints how many programs it compiled and how long that took).
Latencies printed here are a smoke figure, not a benchmark.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

DBLP_VERTICES = 317_080  # SNAP com-DBLP
N_LABELS = 15
VERTICES_PER_PARTITION = 1000
QUERY_SIZES = (5, 6, 8)
N_BEFORE = 32  # queries before the update epoch; a quarter as many after it
N_EDITS = 4  # edge inserts and deletes in the update epoch
VF2_PER_PHASE = 4  # queries of each phase also held to vf2_match
# a smoke run must end within 1,200 s; no single phase waits longer
RUN_LIMIT_S = 1200.0


class CompileLog:
    """Programs JAX compiled (or loaded from the persistent cache) and the
    seconds that took, from JAX's monitoring events; ``phase()`` returns
    and resets the running totals."""

    def __init__(self):
        import jax

        self.n = self.hits = 0
        self.seconds = 0.0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.n += 1
                self.seconds += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def phase(self) -> str:
        out = f"compiles {self.n} ({self.hits} from cache) {self.seconds:.1f} s"
        self.n = self.hits = 0
        self.seconds = 0.0
        return out


class SmokeFailure(Exception):
    pass


def require_tpu(n_chips: int):
    """The devices JAX sees; exits unless they are exactly ``n_chips`` TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(
            f"chip_smoke: no TPU (JAX platform {devices[0].platform!r}); "
            "this script checks the program on the chip only"
        )
    if len(devices) != n_chips:
        sys.exit(
            f"chip_smoke: this phase needs exactly {n_chips} chip(s), JAX sees "
            f"{len(devices)}; the engine's meshes would span all of them"
        )
    return devices


def engine_config(n_vertices: int, seed: int):
    from repro.core import GnnPeConfig

    return GnnPeConfig(
        encoder="monotone",
        index_kind="grouped",
        path_length=2,
        emb_dim=2,
        n_multi=2,
        n_partitions=max(n_vertices // VERTICES_PER_PARTITION, 1),
        seed=seed,
    )


def build(n_vertices: int, seed: int, device, compiles: CompileLog):
    from repro.core import GnnPeEngine
    from repro.graphs import newman_watts_strogatz

    if n_vertices < DBLP_VERTICES:
        print(f"cut: {n_vertices} vertices of com-DBLP's {DBLP_VERTICES} (degree and labels kept)")
    t0 = time.perf_counter()
    g = newman_watts_strogatz(n_vertices, k=6, p=0.1, n_labels=N_LABELS, seed=seed)
    engine = GnnPeEngine(engine_config(n_vertices, seed)).build(g)
    st = engine.offline_stats
    print(
        f"build: {time.perf_counter() - t0:.1f} s for |V|={g.n_vertices} |E|={g.n_edges} "
        f"({len(engine.models)} partitions; train {st['train_time']:.1f} s, embed "
        f"{st['embed_time']:.1f} s, index {st['index_time']:.1f} s): n_paths={st['n_paths']} "
        f"n_groups={st['n_groups']} stacked_bytes={st['stacked_bytes']} "
        f"bytes_in_use={device.memory_stats()['bytes_in_use']}; {compiles.phase()}",
        flush=True,
    )
    return engine


def sample_stream(g, seed: int, n: int) -> list:
    from repro.graphs import random_connected_query

    queries, s = [], seed * 100_000
    while len(queries) < n:
        try:
            queries.append(
                random_connected_query(g, QUERY_SIZES[len(queries) % 3], seed=s)
            )
        except RuntimeError:
            pass
        s += 1
    return queries


def sample_update(g, seed: int):
    import numpy as np

    from repro.core import GraphUpdate

    rng = np.random.default_rng(seed)
    e = g.edge_array()
    return GraphUpdate(
        add_edges=rng.integers(0, g.n_vertices, size=(N_EDITS, 2)),
        remove_edges=e[rng.choice(e.shape[0], size=N_EDITS, replace=False)],
    )


def reference(engine, queries) -> list:
    """The engine's NumPy reference at its current epoch: the stacked
    probe's host leaf stage with the NumPy leaf scan, and the NumPy join."""
    cfg = engine.cfg
    engine.cfg = dataclasses.replace(cfg, use_pallas_scan=False)
    try:
        return engine.match_many(queries, join_impl="numpy")
    finally:
        engine.cfg = cfg


async def _serve(engine, queries, update, join_impl):
    from repro.serve.service import MatchService, ServiceConfig

    svc = MatchService(
        engine,
        ServiceConfig(
            join_impl=join_impl,
            attempt_timeout_s=RUN_LIMIT_S,  # first ticks compile on the chip
        ),
    )
    await svc.start()
    try:
        if update is not None:
            svc.submit_update(update)  # applied before the first query tick
        futs = [svc.submit(q)[1] for q in queries]
        return await asyncio.wait_for(asyncio.gather(*futs), timeout=RUN_LIMIT_S)
    finally:
        await svc.stop(drain=False)


def serve_and_check(engine, queries, update, join_impl, tag, compiles) -> list:
    """Serve one phase through MatchService; check status and exactness."""
    from repro.core import vf2_match

    t0 = time.perf_counter()
    resps = asyncio.run(_serve(engine, queries, update, join_impl))
    t_serve = time.perf_counter() - t0
    bad = [(r.request_id, r.status, r.reason) for r in resps if r.status != "ok"]
    if bad:
        raise SmokeFailure(f"{tag}: {len(bad)} responses not ok: {bad[:4]}")
    served_compiles = compiles.phase()
    t0 = time.perf_counter()
    ref = reference(engine, queries)
    for i, (r, want) in enumerate(zip(resps, ref)):
        got = [tuple(m) for m in r.matches]
        if len(got) != len(want) or set(got) != {tuple(m) for m in want}:
            raise SmokeFailure(
                f"{tag}: query {i} at epoch {engine.epoch}: {len(got)} matches, "
                f"reference {len(want)}"
            )
    t_ref = time.perf_counter() - t0
    t0 = time.perf_counter()
    sample = range(0, len(queries), max(len(queries) // VF2_PER_PHASE, 1))[:VF2_PER_PHASE]
    for i in sample:
        want = vf2_match(engine.graph, queries[i])
        if {tuple(m) for m in resps[i].matches} != set(want):
            raise SmokeFailure(
                f"{tag}: query {i} at epoch {engine.epoch}: {len(resps[i].matches)} "
                f"matches, vf2_match {len(want)}"
            )
    n_matches = sum(len(r.matches) for r in resps)
    print(
        f"{tag}: {len(resps)} ok at epoch {engine.epoch}, {n_matches} matches equal the "
        f"reference, queries {list(sample)} equal vf2_match; serve {t_serve:.1f} s "
        f"({served_compiles}), reference {t_ref:.1f} s ({compiles.phase()}), "
        f"vf2_match {time.perf_counter() - t0:.1f} s",
        flush=True,
    )
    return resps


def run_pass(engine, queries, n_before, seed, join_impl, tag, compiles) -> list:
    resps = serve_and_check(
        engine, queries[:n_before], None, join_impl, f"{tag} before update", compiles
    )
    update = sample_update(engine.graph, seed)
    resps += serve_and_check(
        engine, queries[n_before:], update, join_impl, f"{tag} after update", compiles
    )
    lat = sorted(r.latency_s for r in resps)
    print(f"{tag}: p50 latency {lat[len(lat) // 2] * 1e3:.1f} ms (smoke figure, not a benchmark)")
    return resps


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--vertices", type=int, default=DBLP_VERTICES)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--queries", type=int, default=N_BEFORE,
                    help="queries before the update epoch (a quarter as many after)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    devices = require_tpu(args.chips)
    from repro.compile_cache import enable_compile_cache
    from repro.core import index as index_mod
    from repro.core.matcher import _join_mesh

    print(f"device: {devices[0].device_kind} x{len(devices)}; "
          f"compile cache {enable_compile_cache()}")
    compiles = CompileLog()
    engine = build(args.vertices, args.seed, devices[0], compiles)
    n_before = args.queries
    queries = sample_stream(engine.graph, args.seed, n_before + max(n_before // 4, 1))
    try:
        if args.chips == 1:
            calls0 = index_mod.PALLAS_SCAN_CALLS
            run_pass(engine, queries, n_before, args.seed + 1, "numpy", "pass (a)", compiles)
            calls = index_mod.PALLAS_SCAN_CALLS - calls0
            print(f"pass (a): PALLAS_SCAN_CALLS +{calls}, largest scan "
                  f"T={index_mod.PALLAS_SCAN_MAX_PAIRS} pairs")
            if calls == 0:
                raise SmokeFailure("pass (a): the Pallas leaf scan never ran")
            if index_mod.PALLAS_SCAN_MAX_PAIRS <= 2048:
                raise SmokeFailure("pass (a): no scan spanned more than one 2048-pair block")
        probe = engine.stacked_probe()
        exp0 = probe.host_expansions
        run_pass(engine, queries, n_before, args.seed + 2, "device", "pass (b)", compiles)
        probe_now = engine.stacked_probe()
        exp = probe_now.host_expansions - (exp0 if probe_now is probe else 0)
        join_mesh = _join_mesh()
        n_join = 1 if join_mesh is None else join_mesh.devices.size
        print(f"pass (b): host_expansions +{exp}; probe over {len(probe_now.devices)} "
              f"device(s), join over {n_join}")
        if len(probe_now.devices) != args.chips or n_join != args.chips:
            raise SmokeFailure(f"pass (b): meshes do not span the {args.chips} chip(s)")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    mem = devices[0].memory_stats()
    print(f"memory: bytes_in_use={mem['bytes_in_use']} "
          f"peak_bytes_in_use={mem.get('peak_bytes_in_use')}")
    print(f"total: {time.perf_counter() - t_start:.1f} s")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
