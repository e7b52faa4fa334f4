"""Unified observability layer (obs/): metrics registry semantics,
per-query span tracing with the pruning funnel, and the exporters.

Contracts under test:

- registry: labeled children, histogram bucketing, idempotent
  registration, and EXACT sums under concurrent increments (8 threads);
- tracing: a traced ``match_many`` yields the full stage tree with each
  stage's steps under it (their time inside the stage's), the funnel
  equals the ``PAIR_COUNTERS`` deltas (``surviving_groups`` with or
  without a trace), per-partition rows attribute through
  ``partition_stats()``, and per-stage latencies sum (within slack) to
  the end-to-end wall;
- export: Prometheus text round-trips through the bundled parser, the
  JSON snapshot equals the registry state, and the /metrics endpoint
  serves both;
- service accounting: across a faulted ``MatchService`` run the
  per-status counters sum exactly to submitted — no lost requests; a
  plain run records each request's queue wait, both hand-off legs of
  each tick, and the loop's idle time.

Registry metrics are process-global and cumulative, so every assertion
on engine/service metrics works in deltas, never absolutes.
"""
import asyncio
import json
import threading
import urllib.request

import pytest

from repro.core import GnnPeConfig, GnnPeEngine
from repro.core import index as index_mod
from repro.graphs import erdos_renyi, random_connected_query
from repro.obs import (
    EVENTS,
    REGISTRY,
    TRACER,
    EventLog,
    MetricsHTTPServer,
    MetricsRegistry,
    disable,
    enable,
    parse_prometheus,
    to_prometheus,
    trace_query,
    write_json_snapshot,
)
from repro.dist.probe import StackedProbe
from repro.serve.faults import FaultSpec, FlakyEngine
from repro.serve.service import MatchService, ServiceConfig

# ---------------------------------------------------------------- helpers --


def _base_graph(seed: int = 5):
    return erdos_renyi(150, avg_degree=3.5, n_labels=4, seed=seed)


def _engine(g=None, **overrides):
    g = _base_graph() if g is None else g
    cfg = GnnPeConfig(
        n_partitions=3, encoder="monotone", n_multi=1, block_size=32,
        group_size=4, seed=7, **overrides,
    )
    return GnnPeEngine(cfg).build(g)


def _hist(name, field="sum", **labels):
    """A registry histogram child's sum or count (0 before its first
    observation)."""
    m = REGISTRY.get(name)
    for v in (m.snapshot()["values"] if m is not None else []):
        if all(v["labels"].get(k) == x for k, x in labels.items()):
            return v[field]
    return 0


def _counter_total(name, **labels):
    """Sum of a registry counter over the children matching ``labels``."""
    m = REGISTRY.get(name)
    return sum(
        v["value"]
        for v in (m.snapshot()["values"] if m is not None else [])
        if all(v["labels"].get(k) == x for k, x in labels.items())
    )


def _queries(g, n=4, size=4, seed0=50):
    out, s = [], seed0
    while len(out) < n:
        try:
            out.append(random_connected_query(g, size + len(out) % 3, seed=s))
        except RuntimeError:
            pass
        s += 1
    return out


# ---------------------------------------------------------- registry unit --


def test_counter_labels_and_bare():
    reg = MetricsRegistry()
    c = reg.counter("t_requests_total", "requests", labels=("status",))
    c.labels(status="ok").inc()
    c.labels(status="ok").inc(2)
    c.labels(status="err").inc()
    snap = c.snapshot()
    vals = {tuple(v["labels"].items()): v["value"] for v in snap["values"]}
    assert vals[(("status", "ok"),)] == 3
    assert vals[(("status", "err"),)] == 1
    # a labeled metric refuses bare mutation; a bare one refuses labels()
    with pytest.raises(ValueError):
        c.inc()
    bare = reg.counter("t_ticks_total", "ticks")
    bare.inc(5)
    with pytest.raises(ValueError):
        bare.labels(status="ok")
    assert bare.get() == 5


def test_registry_idempotent_and_type_checked():
    reg = MetricsRegistry()
    a = reg.counter("t_dup_total", "x")
    assert reg.counter("t_dup_total", "x") is a
    with pytest.raises(ValueError):
        reg.gauge("t_dup_total", "x")
    with pytest.raises(ValueError):
        reg.counter("t_dup_total", "x", labels=("k",))


def test_gauge_set_and_histogram_buckets():
    reg = MetricsRegistry()
    g = reg.gauge("t_depth", "queue depth")
    g.set(7)
    g.set(3)
    assert g.get() == 3
    h = reg.histogram("t_lat_seconds", "latency", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()["values"][0]
    assert snap["buckets"] == [0.01, 0.1, 1.0]
    # per-bucket (non-cumulative) counts, +Inf slot last
    assert snap["counts"] == [1, 1, 1, 1]
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(5.555)


def test_concurrent_increments_sum_exactly():
    """8 threads hammering one child must lose no increments — the
    reason children carry a real lock instead of a bare ``+=``."""
    reg = MetricsRegistry()
    c = reg.counter("t_conc_total", "x", labels=("who",))
    child = c.labels(who="all")
    n_threads, per = 8, 10_000

    def work():
        for _ in range(per):
            child.inc()

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert child.value == n_threads * per


def test_disable_makes_mutations_noops():
    reg = MetricsRegistry()
    c = reg.counter("t_off_total", "x")
    try:
        disable()
        c.inc(100)
        with trace_query("q") as tr:
            assert tr is None
    finally:
        enable()
    assert c.get() == 0
    c.inc()
    assert c.get() == 1


# ------------------------------------------------------------- trace tree --


def test_traced_match_many_funnel_and_stages():
    """The acceptance contract: one traced query exposes the full
    pruning funnel (== PAIR_COUNTERS deltas), per-partition probe
    attribution, and stage latencies that sum to the end-to-end wall."""
    eng = _engine(index_kind="grouped")
    qs = _queries(eng.graph, n=3)
    eng.match_many(qs)  # warm compile outside the trace
    TRACER.trace_rate = 1.0
    before = dict(index_mod.PAIR_COUNTERS)
    rows_before = [p["probe_rows"] for p in eng.partition_stats()]
    with trace_query("probe-test") as tr:
        assert tr is not None
        eng.match_many(qs)
    after = dict(index_mod.PAIR_COUNTERS)

    # funnel == the global pair-counter deltas for this batch
    assert tr.funnel["leaf_pairs"] == after["leaf_pairs"] - before["leaf_pairs"]
    assert tr.funnel["group_pairs"] == after["group_pairs"] - before["group_pairs"]
    assert tr.funnel["leaf_pairs"] > 0
    assert 0 < tr.funnel["surviving_groups"]
    assert 0 < tr.funnel["candidates"] <= tr.funnel["leaf_pairs"]
    assert 0 <= tr.funnel["matches"] <= tr.funnel["candidates"]
    assert 0.0 <= tr.pruning_power() <= 1.0

    # stage tree: embed/plan/probe/assemble/join all present, once each
    for name in ("embed", "plan", "probe", "assemble", "join"):
        assert len(tr.root.find(name)) == 1, name
    # per-partition attribution through partition_stats(): the rows
    # each partition served this batch sum to the batch's candidates
    parts = eng.partition_stats()
    assert len(parts) <= eng.cfg.n_partitions
    rows = [p["probe_rows"] - b for p, b in zip(parts, rows_before)]
    assert all(r >= 0 for r in rows)
    assert sum(rows) == tr.funnel["candidates"] > 0
    for p in parts:
        assert p["delta_rows"] == 0  # no deltas applied yet
    assert not tr.root.find("partition")  # no per-partition spans

    # stage latencies sum (within slack) to the traced wall time
    stage_s = sum(
        s.duration_s
        for s in tr.root.children
        if s.name in ("cache_lookup", "embed", "plan", "probe", "assemble",
                      "join", "cache_store")
    )
    wall = tr.root.duration_s
    assert stage_s <= wall * 1.01 + 1e-6
    assert stage_s >= wall * 0.5, (stage_s, wall)

    # the trace landed in the ring and serialises
    assert any(t is tr for t in TRACER.recent())
    d = tr.as_dict()
    assert d["funnel"] == tr.funnel
    json.dumps(d)  # round-trippable


STEPS = {
    "embed": ("stars", "encode", "wait"),
    "probe": ("prepare", "wait", "slice", "account"),
    "join": ("prepare", "wait", "collect"),
}


@pytest.mark.parametrize("join_impl", ["device", "numpy"])
def test_traced_match_many_records_every_step(monkeypatch, join_impl):
    """Each stage's steps sit under its span and inside its time, in the
    trace and in the step histogram; the served path (device join) has
    every step, the NumPy join the embedding's, the probe's host-side
    prepare and none of the join's.  Device waits are counted, and a
    trace no longer makes the stacked probe build per-partition stats."""
    eng = _engine(index_kind="grouped", join_impl=join_impl)
    qs = _queries(eng.graph, n=3)
    eng.match_many(qs)  # warm compile outside the trace

    def no_stats(*a, **kw):
        raise AssertionError("per-partition stats built without a dr cost model")

    monkeypatch.setattr(StackedProbe, "_device_probe_stats", no_stats)
    served = join_impl == "device"
    host_steps = {"embed": STEPS["embed"], "probe": ("prepare",), "join": ()}
    syncs0 = _counter_total("gnnpe_engine_device_syncs_total")
    stage0 = {s: _hist("gnnpe_engine_stage_seconds", stage=s) for s in STEPS}
    step0 = {
        (s, x): _hist("gnnpe_engine_step_seconds", stage=s, step=x)
        for s, xs in STEPS.items() for x in xs
    }
    TRACER.trace_rate = 1.0
    with trace_query("steps") as tr:
        eng.match_many(qs)
    for stage, steps in STEPS.items():
        (span,) = tr.root.find(stage)
        want = steps if served else host_steps[stage]
        assert {c.name for c in span.children} == {f"{stage}.{x}" for x in want}, stage
        assert sum(c.duration_s for c in span.children) <= span.duration_s
        stage_s = _hist("gnnpe_engine_stage_seconds", stage=stage) - stage0[stage]
        step_s = [
            _hist("gnnpe_engine_step_seconds", stage=stage, step=x) - step0[(stage, x)]
            for x in steps
        ]
        assert all(v > 0 for v in step_s[: len(want)]) and sum(step_s) <= stage_s
    syncs = _counter_total("gnnpe_engine_device_syncs_total") - syncs0
    assert syncs >= (1 + 3 + 3 if served else 1)  # embed; probe; join init, compact, refine


def test_surviving_groups_counted_without_a_trace(monkeypatch):
    """The surviving-groups rung is always on: untraced, the stacked
    probe's host and device leaf stages each count what the per-partition
    reference traversal (``query_index_batch_multi``, which shares no code
    with the stacked descent) counts on the same probes."""
    eng = _engine(index_kind="grouped")
    qs = _queries(eng.graph, n=3)
    assert TRACER.current() is None
    probes = []
    for name in ("probe", "probe_device"):
        def record(self, q_emb, q_emb0, q_multi=None, q_label_hash=None, *a,
                   _orig=getattr(StackedProbe, name), **kw):
            probes.append((q_emb, q_emb0, q_multi, q_label_hash))
            return _orig(self, q_emb, q_emb0, q_multi, q_label_hash, *a, **kw)

        monkeypatch.setattr(StackedProbe, name, record)

    def reference(q_emb, q_emb0, q_multi, qh):
        items = [
            (m.index, q_emb[mi], q_emb0[mi], None if q_multi is None else q_multi[:, mi], qh)
            for mi, m in enumerate(eng.models)
            if m.index.n_paths
        ]
        before = index_mod._SURVIVING_GROUPS.value
        index_mod.query_index_batch_multi(items, use_pallas=False, use_groups=True)
        return index_mod._SURVIVING_GROUPS.value - before

    counts = {}
    for jimpl in ("numpy", "device"):
        eng.match_many(qs, join_impl=jimpl)  # warm
        probes.clear()
        before = _counter_total("gnnpe_funnel_total", stage="surviving_groups")
        eng.match_many(qs, join_impl=jimpl)
        counts[jimpl] = _counter_total("gnnpe_funnel_total", stage="surviving_groups") - before
        assert len(probes) == 1, (jimpl, len(probes))
        counts[f"{jimpl} reference"] = reference(*probes[0])
    assert counts["numpy"] > 0
    assert len(set(counts.values())) == 1, counts


def test_trace_sampling_deterministic():
    TRACER.clear()
    old = TRACER.trace_rate
    try:
        TRACER.trace_rate = 0.25
        sampled = 0
        for i in range(40):
            with trace_query(i) as tr:
                sampled += tr is not None
        assert sampled == 10  # exactly rate * n, no RNG
    finally:
        TRACER.trace_rate = old


# --------------------------------------------------------------- exporters --


def test_prometheus_round_trip_and_json_snapshot(tmp_path):
    reg = MetricsRegistry()
    c = reg.counter("t_rt_total", "reqs", labels=("status",))
    c.labels(status="ok").inc(3)
    c.labels(status='we"ird\\').inc()  # escaping
    h = reg.histogram("t_rt_seconds", "lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    g = reg.gauge("t_rt_depth", "depth")
    g.set(4)

    text = to_prometheus(reg.snapshot())
    assert "# TYPE t_rt_total counter" in text
    assert "# TYPE t_rt_seconds histogram" in text
    parsed = parse_prometheus(text)
    assert parsed['t_rt_total{status="ok"}'] == 3
    assert parsed['t_rt_seconds_bucket{le="0.1"}'] == 1
    assert parsed['t_rt_seconds_bucket{le="1"}'] == 2  # cumulative
    assert parsed['t_rt_seconds_bucket{le="+Inf"}'] == 2
    assert parsed["t_rt_seconds_count"] == 2
    assert parsed["t_rt_seconds_sum"] == pytest.approx(0.55)
    assert parsed["t_rt_depth"] == 4
    with pytest.raises(ValueError):
        parse_prometheus("not a metric line at all{")

    path = tmp_path / "snap.json"
    write_json_snapshot(path, reg.snapshot(), extra={"run": "t"})
    doc = json.loads(path.read_text())
    assert doc["run"] == "t"
    assert doc["metrics"] == reg.snapshot()


def test_metrics_http_endpoint():
    reg = MetricsRegistry()
    reg.counter("t_http_total", "x").inc(2)
    with MetricsHTTPServer(port=0, registry=reg) as srv:
        body = urllib.request.urlopen(srv.url).read().decode()
        assert "t_http_total 2" in body
        js = urllib.request.urlopen(srv.url + ".json").read().decode()
        assert json.loads(js)["t_http_total"]["type"] == "counter"


def test_event_log_lines(tmp_path):
    path = tmp_path / "events.jsonl"
    log = EventLog()
    assert not log.active
    log.to_path(path)
    assert log.active
    log.emit("request", rid=1, status="ok")
    log.emit("host_loss", host=2)
    log.close()
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert [e["event"] for e in lines] == ["request", "host_loss"]
    assert lines[0]["rid"] == 1 and "ts" in lines[0]


# ------------------------------------------------- service accounting -----


def _status_counts():
    """Per-status completion counts from the registry histogram."""
    m = REGISTRY.get("gnnpe_service_request_seconds")
    out = {}
    for v in m.snapshot()["values"]:
        out[v["labels"]["status"]] = v["count"]
    return out


def test_faulted_service_counters_sum_to_submitted():
    """Zero lost requests, provable from counters alone: across a run
    with a poisoned query and forced sheds, every submitted request
    lands in exactly one terminal status — in the service's own
    counters AND in the registry deltas behind /metrics."""
    g = _base_graph()
    eng = _engine(g)
    qs = _queries(g, n=8)
    flaky = FlakyEngine(eng, FaultSpec(poison=lambda q: q is qs[5]))
    svc = MatchService(flaky, ServiceConfig(
        max_batch=4, idle_tick_s=0.02, backoff_base_s=0.005,
        cache_fastpath=False,
    ))
    before = _status_counts()

    async def run():
        await svc.start()
        futs = [svc.submit(q)[1] for q in qs]
        resps = await asyncio.gather(*futs)
        await svc.stop()
        return resps

    resps = asyncio.run(run())
    c = svc.counters
    statuses = ("ok", "rejected", "shed", "expired", "error", "retry-exhausted")
    assert sum(c[s] for s in statuses) == c["submitted"] == len(qs)
    assert c["error"] == 1 and c["ok"] == len(qs) - 1
    assert sum(1 for r in resps if r.status == "error") == 1

    after = _status_counts()
    deltas = {k: after.get(k, 0) - before.get(k, 0) for k in after}
    assert sum(deltas.values()) == len(qs)
    assert deltas.get("error", 0) == 1 and deltas.get("ok", 0) == len(qs) - 1

    # and the same numbers survive the Prometheus round trip
    parsed = parse_prometheus(to_prometheus())
    assert parsed['gnnpe_service_request_seconds_count{status="error"}'] >= 1


def test_service_records_queue_wait_handoff_and_idle():
    """One queue wait per request, both hand-off legs per tick, and idle
    time while nothing is queued — untraced, from the registry alone."""
    g = _base_graph()
    eng = _engine(g)
    qs = _queries(g, n=4)
    svc = MatchService(eng, ServiceConfig(
        max_batch=1, schedule="fifo", idle_tick_s=0.02, cache_fastpath=False,
        trace_rate=0.0,
    ))
    names = {
        "queue": ("gnnpe_service_queue_wait_seconds", {}),
        "to_engine": ("gnnpe_service_handoff_seconds", {"leg": "to_engine"}),
        "to_loop": ("gnnpe_service_handoff_seconds", {"leg": "to_loop"}),
        "idle": ("gnnpe_service_idle_seconds", {}),
    }
    before = {k: (_hist(n, "count", **lb), _hist(n, **lb)) for k, (n, lb) in names.items()}

    async def run():
        await svc.start()
        await asyncio.sleep(0.05)  # nothing queued: the loop idles
        for q in qs:  # one at a time, so each request is its own tick
            assert (await svc.submit(q)[1]).ok
        await svc.stop()

    old_rate = TRACER.trace_rate
    try:
        asyncio.run(run())
    finally:
        TRACER.trace_rate = old_rate
    delta = {
        k: (_hist(n, "count", **lb) - before[k][0], _hist(n, **lb) - before[k][1])
        for k, (n, lb) in names.items()
    }
    n_ticks = len(svc.tick_stats())
    assert n_ticks == len(qs)
    assert delta["queue"][0] == len(qs) and delta["queue"][1] >= 0
    assert delta["to_engine"][0] == delta["to_loop"][0] == n_ticks
    assert delta["to_engine"][1] > 0 and delta["to_loop"][1] > 0
    assert delta["idle"][0] >= 1 and delta["idle"][1] > 0
