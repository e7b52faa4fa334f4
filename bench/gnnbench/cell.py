"""One run of one cell: build from the seed, warm up, measure, check.

The window drives ``MatchService.submit`` and ``MatchService.submit_update``
open-loop, on the benchmark's own clock.  Two thin wrappers on the service's
inner ``MatchServer`` instance observe, on the engine thread, how many
updates each query tick saw and when each update tick ended; they change
nothing the server does.  After the window every answer is held to the
plain reference at the epoch it was served at (``reference.py``).
"""
from __future__ import annotations

import asyncio
import copy
import dataclasses
import importlib.util
import json
import math
import time
from pathlib import Path

import numpy as np

from . import gen, tracefile
from .reference import RefGraph

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent
CLOSE_WAIT_S = 60.0  # answers may come this long after the window closes


# ---------------------------------------------------------------- spec ----
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    rate: float | None  # offered operations per second, measured for this cell


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def resolve(spec: dict, workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``: its configuration, traffic mix and
    metrics, each found by name, and its offered rate from
    ``bench/cells/<workload>.json`` (None until a sweep has measured it)."""
    matches = [w for w in spec["workloads"] if w["name"] == workload]
    if not matches:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = matches[0]
    cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
    with open(root / cfg["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    rate_file = root / "bench" / "cells" / f"{workload}.json"
    rate = None
    if rate_file.exists():
        with open(rate_file) as f:
            rate = float(json.load(f)["rate_ops_per_s"])
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=config,
        traffic=traffic,
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, workload)],
        rate=rate,
    )


def reader(name: str, root: Path = ROOT):
    """``read(run)`` of ``bench/metrics/<name>.py``."""
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def load_peaks(root: Path = ROOT) -> dict:
    with open(root / "bench" / "peaks.json") as f:
        return json.load(f)


# ------------------------------------------------------------- records ----
class CompileLog:
    """Programs XLA compiled, and persistent-cache hits, from JAX's
    monitoring events (the listener stays registered for the process)."""

    def __init__(self):
        import jax

        self.compiles = self.hits = 0
        self.compile_s = 0.0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
                self.compile_s += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def mark(self) -> tuple:
        return self.compiles, self.hits, self.compile_s


@dataclasses.dataclass
class Request:
    pool_index: int
    due: float
    query: object  # this request's own copy of the pool query
    done_at: float | None = None
    response: object = None


@dataclasses.dataclass
class Run:
    """What one run recorded; the metric readers read this."""

    cell: Cell
    seed: int
    seconds: float
    peaks: dict
    setup_s: float = 0.0
    setup_parts: dict = dataclasses.field(default_factory=dict)
    requests: list = dataclasses.field(default_factory=list)  # window requests
    update_due: list = dataclasses.field(default_factory=list)  # (stream index, due)
    update_ticks: list = dataclasses.field(default_factory=list)  # (t_end, n applied)
    query_ticks: list = dataclasses.field(default_factory=list)  # (t0, t1, n applied, ids)
    admit_s: list = dataclasses.field(default_factory=list)
    lateness_s: list = dataclasses.field(default_factory=list)
    window: tuple = (0.0, 0.0)  # perf_counter at open and close
    drained_at: float = 0.0
    counters0: dict = dataclasses.field(default_factory=dict)
    counters1: dict = dataclasses.field(default_factory=dict)
    service_counters: dict = dataclasses.field(default_factory=dict)
    compiles_in_window: int = 0
    compile_hits_in_window: int = 0
    queue_at_open: int = 0
    queue_at_close: int = 0
    host_spans: list = dataclasses.field(default_factory=list)  # (name, t0, t1)
    trace_open: float = 0.0  # perf_counter when the trace's window annotation opened
    trace_events: list | None = None
    trace: dict | None = None
    device: dict = dataclasses.field(default_factory=dict)
    reference_s: float = 0.0

    # -- helpers for readers -------------------------------------------
    def window_ticks(self) -> list:
        w0, w1 = self.window
        return [t for t in self.query_ticks if w0 <= t[0] < w1]

    def match_latencies_s(self) -> list:
        """From due time to response, every request due in the window;
        one that was not served counts as slower than every served one."""
        served = [
            r.done_at - r.due for r in self.requests
            if r.response is not None and r.response.status == "ok"
        ]
        worst = max(served + [self.drained_at - self.window[0]]) + 1e-3
        return served + [worst] * (len(self.requests) - len(served))

    def update_visible_s(self) -> list:
        out = []
        for u, due in self.update_due:
            t_vis = next((t for t, n in self.update_ticks if n >= u + 1), None)
            out.append((t_vis if t_vis is not None else math.inf) - due)
        return out

    def counter(self, name: str, **labels) -> float:
        """Window delta of a program counter (or a histogram's count)."""
        return _metric_value(self.counters1, name, labels, "value") - _metric_value(
            self.counters0, name, labels, "value"
        )

    def hist_sum(self, name: str, **labels) -> float:
        return _metric_value(self.counters1, name, labels, "sum") - _metric_value(
            self.counters0, name, labels, "sum"
        )

    def queries_in_window(self) -> int:
        return sum(len(t[3]) for t in self.window_ticks())


def _metric_value(snapshot: dict, name: str, labels: dict, field: str) -> float:
    m = snapshot.get(name)
    if m is None:
        return 0.0
    for v in m["values"]:
        if all(v["labels"].get(k) == val for k, val in labels.items()):
            if "value" in v and field == "value":
                return float(v["value"])
            if field == "value":
                return float(v["count"])
            return float(v[field])
    return 0.0


def percentile(values: list, q: float) -> float | None:
    """Nearest-rank percentile (a value that occurred)."""
    if not values:
        return None
    s = sorted(values)
    return s[max(math.ceil(q / 100.0 * len(s)) - 1, 0)]


# ---------------------------------------------------------------- build ----
def engine_config(config: dict, seed: int):
    from repro.core import GnnPeConfig

    e = dict(config["engine"])
    per_part = e.pop("vertices_per_partition")
    n = int(config["vertices"])
    return GnnPeConfig(**e, n_partitions=max(n // per_part, 1), seed=seed)


def program_graph(n: int, labels, edges):
    from repro.graphs import Graph

    offsets, nbrs = gen.csr(n, edges)
    return Graph(offsets=offsets, nbrs=nbrs, labels=np.asarray(labels, np.int32))


def program_update(u: gen.EdgeUpdate):
    from repro.core import GraphUpdate

    return GraphUpdate(add_edges=u.add, remove_edges=u.remove)


def _wrap_server(server, run: Run, harvest_spans: bool) -> None:
    """Observe the inner server's ticks on the engine thread."""
    from repro.obs.trace import TRACER

    exec_batch, update_tick = server.execute_batch, server.apply_update_tick

    def execute_batch(queries, isolate=False):
        k = server.n_updates_applied
        t0 = time.perf_counter()
        out = exec_batch(queries, isolate)
        t1 = time.perf_counter()
        run.query_ticks.append((t0, t1, k, [id(q) for q in queries]))
        if harvest_spans:
            run.host_spans.append(("bench.query_tick", t0, t1))
            tr = TRACER.current()
            if tr is not None:
                run.host_spans.extend(
                    (f"stage.{s.name}", s.t0, s.t1)
                    for s in tr.root.children
                    if s.t1 is not None and s.t0 >= t0
                )
        return out

    def apply_update_tick():
        t0 = time.perf_counter()
        n = update_tick()
        t1 = time.perf_counter()
        run.update_ticks.append((t1, server.n_updates_applied))
        if harvest_spans:
            run.host_spans.append(("bench.update_tick", t0, t1))
        return n

    server.execute_batch = execute_batch
    server.apply_update_tick = apply_update_tick


async def _drive(svc, run: Run, graphs, updates, ops, seconds, record: bool, on_open=None):
    """Send ``ops`` on their schedule; return (requests, open, close)."""
    reqs: list[Request] = []
    t_open = time.perf_counter()
    if on_open is not None:
        on_open(t_open)
    for op in ops:
        due = t_open + op.due_s
        delay = due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        sent = time.perf_counter()
        if record:
            run.lateness_s.append(sent - due)
        if op.kind == "match":
            r = Request(op.index, due, copy.copy(graphs[op.index]))
            t_a = time.perf_counter()
            _, fut = svc.submit(r.query)
            if record:
                run.admit_s.append(time.perf_counter() - t_a)

            def done(f, r=r):
                r.done_at = time.perf_counter()
                r.response = f.result()

            fut.add_done_callback(done)
            reqs.append(r)
        else:
            svc.submit_update(updates[op.index])
            if record:
                run.update_due.append((op.index, due))
    t_close = t_open + seconds
    if time.perf_counter() < t_close:
        await asyncio.sleep(t_close - time.perf_counter())
    return reqs, t_open, t_close


async def _wait_answers(svc, reqs, n_updates: int, limit_s: float) -> None:
    t_end = time.perf_counter() + limit_s
    while time.perf_counter() < t_end:
        if all(r.response is not None for r in reqs) and (
            svc.server.n_updates_applied >= n_updates
        ):
            return
        await asyncio.sleep(0.005)


def _registry_snapshot() -> dict:
    from repro.obs.metrics import REGISTRY

    return REGISTRY.snapshot()


# ------------------------------------------------------------------ run ----
def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, t_process0: float,
             log=print, trace_dir: str | None = None, sweep: list | None = None):
    """One run of ``cell``.  Returns the run record and the checks.

    ``sweep`` lists offered rates: the window is then repeated once per
    rate after the one set-up, each step drained before the next, and a
    line per step is logged (the answers of the last step are checked).
    """
    import jax
    import jax.profiler

    from repro.core import GnnPeEngine
    from repro.serve.admission import AdmissionConfig, TenantQuota
    from repro.serve.service import MatchService, ServiceConfig

    traffic, config = cell.traffic, cell.config
    run = Run(cell, seed, float(seconds), load_peaks())
    compiles = CompileLog()
    parts = run.setup_parts
    share, n_pool = traffic["update_share"], sum(int(s["count"]) for s in traffic["pool"])

    t = time.perf_counter()
    g = gen.data_graph(config, config["data_seed"])
    pool = gen.query_pool(g, traffic["pool"], config["data_seed"])
    if sweep is None and cell.rate is None:
        raise ValueError(f"{cell.name}: no offered rate; measure one with --sweep")
    rates = [cell.rate] if sweep is None else list(sweep)
    step_ops = [
        gen.schedule(r, seconds, share, n_pool, seed if sweep is None else seed + 2 + i)
        for i, r in enumerate(rates)
    ]
    # one update stream: before each window, its warm-up replay's own
    # updates, then the window's, so that every update takes effect once
    n_upd = [sum(op.kind == "update" for op in ops) for ops in step_ops]
    starts = np.cumsum([0] + [2 * n for n in n_upd])
    stream = gen.update_stream(
        g, int(starts[-1]), traffic["update"]["inserts"], traffic["update"]["deletes"], seed
    ) if starts[-1] else []

    def shifted(ops, off):
        return [dataclasses.replace(op, index=op.index + int(off)) if op.kind == "update"
                else op for op in ops]

    warm_ops = [shifted(ops, a) for ops, a in zip(step_ops, starts[:-1])]
    step_ops = [shifted(ops, a + n) for ops, a, n in zip(step_ops, starts[:-1], n_upd)]
    graphs = [program_graph(len(q.labels), q.labels, q.edges) for q in pool]
    prog_updates = [program_update(u) for u in stream]
    parts["generate_s"] = time.perf_counter() - t

    log(f"bench: generated the data and the traffic in {parts['generate_s']:.1f} s; building")
    t = time.perf_counter()
    c0 = compiles.mark()
    engine = GnnPeEngine(engine_config(config, seed)).build(program_graph(g.n, g.labels, g.edges))
    parts["build_s"] = time.perf_counter() - t
    t = time.perf_counter()
    if config["engine"].get("probe_impl") == "stacked":
        engine.stacked_probe()  # stacks every partition's index for the device
    parts["stack_s"] = time.perf_counter() - t
    c1 = compiles.mark()
    parts["build_programs"] = c1[0] - c0[0]
    parts["build_cache_loads"] = c1[1] - c0[1]
    log(f"bench: built |V|={g.n} |E|={len(g.edges)} partitions={len(engine.models)} "
        f"n_paths={engine.offline_stats.get('n_paths')} "
        f"stacked_bytes={engine.offline_stats.get('stacked_bytes')}")

    q = traffic["admission"]
    quota = TenantQuota(
        rate=float("inf") if q["rate"] is None else float(q["rate"]),
        burst=float(q["burst"]), max_backlog=int(q["max_backlog"]),
    )
    svc = MatchService(
        engine,
        ServiceConfig(
            probe_impl=config["engine"].get("probe_impl"),
            join_impl=config["engine"].get("join_impl"),
            **traffic["service"],
        ),
        AdmissionConfig(default_quota=quota),
    )
    _wrap_server(svc.server, run, harvest_spans=trace)

    async def serve_in_order(indices):
        for i in indices:  # one at a time: each its own tick, in this order
            await svc.submit(copy.copy(graphs[i]))[1]

    async def warm_up(ops, n_applied_after, first: bool):
        """Compile, outside the window, every program the window will run.

        The program compiles per query shape and per pair-cap guess of
        the device join, a guess each join signature carries over from
        the query that used it last.  So: every pool query once; then each
        query once more, ordered by its last arrival in the window, which
        leaves the guesses as the window will leave them; then the
        window's own schedule, replayed with updates of its own.  With one
        query per tick in arrival order, the window then repeats the
        replay's sequence of programs."""
        t_w = time.perf_counter()
        cw0 = compiles.mark()
        if first:
            await serve_in_order(range(len(graphs)))
            log(f"bench: warm-up served the pool's {len(graphs)} queries in "
                f"{time.perf_counter() - t_w:.1f} s ({compiles.mark()[0] - cw0[0]} programs)")
        last = {op.index: i for i, op in enumerate(ops) if op.kind == "match"}
        await serve_in_order(sorted(last, key=last.get))
        wreqs, _, _ = await _drive(svc, run, graphs, prog_updates, ops, seconds, False)
        await _wait_answers(svc, wreqs, n_applied_after, CLOSE_WAIT_S * 5)
        t_c = time.perf_counter() + CLOSE_WAIT_S
        while engine.pending_compactions() and time.perf_counter() < t_c:
            await asyncio.sleep(0.01)  # let warm-up compactions install
        cw1 = compiles.mark()
        log(f"bench: warm-up done in {time.perf_counter() - t_w:.1f} s "
            f"({cw1[0] - cw0[0]} programs compiled or loaded, {cw1[1] - cw0[1]} of them "
            f"loaded from the cache)")
        parts["warmup_s"] = time.perf_counter() - t_w
        parts["warmup_programs"] = cw1[0] - cw0[0]
        parts["warmup_cache_loads"] = cw1[1] - cw0[1]
        parts["warmup_compile_s"] = cw1[2] - cw0[2]
        return sum(r.response is None for r in wreqs)

    async def window(ops, n_applied_after, traced):
        run.lateness_s, run.admit_s, run.update_due = [], [], []
        marks, trace_cm = {}, None
        if traced:
            jax.profiler.start_trace(trace_dir)

        def on_open(t_open):
            nonlocal trace_cm
            run.setup_s = t_open - t_process0
            run.counters0 = _registry_snapshot()
            run.service_counters["compactions0"] = svc.counters["compactions_installed"]
            marks["open"] = compiles.mark()
            if traced:
                trace_cm = jax.profiler.TraceAnnotation(tracefile.WINDOW)
                trace_cm.__enter__()
                run.trace_open = t_open

        reqs, t_open, t_close = await _drive(
            svc, run, graphs, prog_updates, ops, seconds, True, on_open
        )
        if trace_cm is not None:
            trace_cm.__exit__(None, None, None)
        cm = compiles.mark()
        run.compiles_in_window = cm[0] - marks["open"][0]
        run.compile_hits_in_window = cm[1] - marks["open"][1]
        run.counters1 = _registry_snapshot()
        run.service_counters["compactions1"] = svc.counters["compactions_installed"]
        run.window = (t_open, t_close)
        run.queue_at_close = sum(r.response is None for r in reqs)
        run.requests = reqs
        log(f"bench: window closed: {len(reqs)} match requests, "
            f"{run.compiles_in_window} programs compiled or loaded in it")
        if traced:
            jax.profiler.stop_trace()
            log(f"bench: trace written in {time.perf_counter() - t_close:.1f} s")
        await _wait_answers(svc, reqs, n_applied_after, CLOSE_WAIT_S)
        run.drained_at = time.perf_counter()

    async def main():
        await svc.start()
        try:
            for i, (rate, ops) in enumerate(zip(rates, step_ops)):
                run.queue_at_open = await warm_up(warm_ops[i], int(starts[i]) + n_upd[i], i == 0)
                await window(ops, int(starts[i + 1]), trace and sweep is None)
                if sweep is not None:
                    lat = run.match_latencies_s()
                    log(f"sweep: offered {rate} op/s: {len(run.requests)} matches, "
                        f"p50 {percentile(lat, 50) * 1e3:.1f} ms, "
                        f"p95 {percentile(lat, 95) * 1e3:.1f} ms, "
                        f"queue at close {run.queue_at_close}, "
                        f"drained {run.drained_at - run.window[1]:.2f} s after close, "
                        f"compiles {run.compiles_in_window}")
        finally:
            await svc.stop(drain=False)

    asyncio.run(main())
    devices = jax.devices()
    run.device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices
        ),
    }
    if trace and sweep is None:
        events = tracefile.load_events(trace_dir)
        run.trace_events = events + tracefile.host_spans_to_events(
            run.host_spans, run.trace_open, events
        )
        run.trace = tracefile.reduce(run.trace_events)

    final_edges = _program_edges(engine.graph)
    del engine, svc
    t = time.perf_counter()
    checks = check_answers(run, g, stream, final_edges)
    run.reference_s = time.perf_counter() - t
    return run, checks


def _program_edges(graph) -> set:
    offsets = np.asarray(graph.offsets)
    nbrs = np.asarray(graph.nbrs).astype(np.int64)
    src = np.repeat(np.arange(len(offsets) - 1, dtype=np.int64), np.diff(offsets))
    keep = src < nbrs
    return set(zip(src[keep].tolist(), nbrs[keep].tolist()))


# ---------------------------------------------------------------- check ----
def check_answers(run: Run, g: gen.DataGraph, stream: list, final_edges: set) -> dict:
    """Every window answer against the reference at the epoch it was
    served at, and the graph after the whole stream.

    ``wrong_answers``: served match sets that differ from the reference
    (a missing, extra or repeated embedding).  ``lost_answers``: requests
    that never resolved or resolved with an error (a refusal under
    admission or shedding is an outcome of load, not an answer, and is
    counted in ``failed`` instead).  ``edge_diff``: edges on which the
    served graph after the stream and the reference disagree.
    """
    tick_of = {}
    for _t0, _t1, k, ids in run.window_ticks() + [
        t for t in run.query_ticks if t[0] >= run.window[1]
    ]:
        for i in ids:
            tick_of[i] = k
    by_k: dict = {}
    lost = 0
    for r in run.requests:
        status = None if r.response is None else r.response.status
        if status == "ok" and id(r.query) in tick_of:
            by_k.setdefault(tick_of[id(r.query)], []).append(r)
        elif status not in ("rejected", "shed"):
            lost += 1
    ref = RefGraph(g.n, g.labels, g.edges)
    applied, wrong = 0, 0
    pool = {}
    for k in sorted(by_k):
        while applied < k:
            ref.apply(stream[applied].add, stream[applied].remove)
            applied += 1
        expect: dict = {}
        for r in by_k[k]:
            if r.pool_index not in expect:
                q = r.query
                expect[r.pool_index] = ref.match(q.labels, _query_edges(q))
            got = [tuple(int(x) for x in m) for m in r.response.matches]
            if len(got) != len(set(got)) or set(got) != expect[r.pool_index]:
                wrong += 1
        pool.update(expect)
    while applied < len(stream):
        ref.apply(stream[applied].add, stream[applied].remove)
        applied += 1
    checks = {
        "wrong_answers": {"value": wrong, "limit": 0},
        "lost_answers": {"value": lost, "limit": 0},
    }
    if stream:
        checks["edge_diff"] = {"value": len(ref.edge_set() ^ final_edges), "limit": 0}
    return checks


def _query_edges(q) -> np.ndarray:
    offsets, nbrs = np.asarray(q.offsets), np.asarray(q.nbrs)
    src = np.repeat(np.arange(len(offsets) - 1), np.diff(offsets))
    keep = src < nbrs
    return np.stack([src[keep], nbrs[keep]], axis=1)


def correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


CACHE_DIR = ROOT / ".jax_cache"  # JAX's persistent compilation cache, in the checkout
