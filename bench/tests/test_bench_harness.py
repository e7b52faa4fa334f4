"""The chip benchmark's harness, on the CPU: generators, reference, trace
reduction, byte function, the resolution of cells by name, and whole runs
of a tiny cell with the served path sound and broken underneath."""
from __future__ import annotations

import copy
import itertools
import json
import os
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
DATA = Path(__file__).resolve().parent / "data"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

from gnnbench import cell as cellmod  # noqa: E402
from gnnbench import gen, roofline, tracefile  # noqa: E402
from gnnbench.reference import RefGraph  # noqa: E402

SMALL = {"generator": "nws", "vertices": 300, "k": 6, "p": 0.1, "labels": 5,
         "label_dist": "uniform"}


# ------------------------------------------------------------ generators ----
def test_generators_repeat_from_the_seed():
    big = 2**31 + 12345  # seeds run past 32 signed bits
    g1, g2 = gen.data_graph(SMALL, big), gen.data_graph(SMALL, big)
    assert np.array_equal(g1.edges, g2.edges) and np.array_equal(g1.labels, g2.labels)
    assert not np.array_equal(g1.edges, gen.data_graph(SMALL, big + 1).edges)
    pool_spec = [{"size": 4, "count": 3, "density": "any"},
                 {"size": 8, "count": 2, "density": "dense"},
                 {"size": 8, "count": 2, "density": "sparse"}]
    p1, p2 = gen.query_pool(g1, pool_spec, big), gen.query_pool(g2, pool_spec, big)
    assert all(np.array_equal(a.edges, b.edges) and np.array_equal(a.labels, b.labels)
               for a, b in zip(p1, p2))
    s1 = gen.schedule(20.0, 5.0, 0.2, len(p1), big)
    assert s1 == gen.schedule(20.0, 5.0, 0.2, len(p1), big)
    u1 = gen.update_stream(g1, 6, 4, 4, big)
    u2 = gen.update_stream(g2, 6, 4, 4, big)
    assert all(np.array_equal(a.add, b.add) and np.array_equal(a.remove, b.remove)
               for a, b in zip(u1, u2))


def test_schedule_offers_the_same_work_on_every_seed():
    a = gen.schedule(20.0, 10.0, 0.2, 32, 1)
    b = gen.schedule(20.0, 10.0, 0.2, 32, 2)
    assert len(a) == len(b) == 200
    assert sum(op.kind == "update" for op in a) == sum(op.kind == "update" for op in b) == 40
    gaps = lambda ops: sorted(np.diff([op.due_s for op in ops]).round(9))  # noqa: E731
    assert a != b
    # the same gaps in another order (one gap is left out by the first op)
    assert len(set(gaps(a)) ^ set(gaps(b))) <= 2
    assert max(op.due_s for op in a) < 10.0
    picks = [op.index for op in a if op.kind == "match"]
    assert max(picks.count(i) for i in range(32)) - min(picks.count(i) for i in range(32)) <= 1


def test_query_pool_sizes_and_density():
    g = gen.data_graph(SMALL, 7)
    pool = gen.query_pool(g, [{"size": 4, "count": 4, "density": "any"},
                              {"size": 8, "count": 4, "density": "dense"},
                              {"size": 8, "count": 4, "density": "sparse"}], 7)
    assert [len(q.labels) for q in pool] == [4] * 4 + [8] * 8
    assert all(q.avg_degree > 3 for q in pool[4:8])
    assert all(q.avg_degree <= 3 for q in pool[8:])
    for q in pool:  # connected
        seen, todo = {0}, [0]
        while todo:
            v = todo.pop()
            for u, w in q.edges.tolist():
                for a, b in ((u, w), (w, u)):
                    if a == v and b not in seen:
                        seen.add(b)
                        todo.append(b)
        assert len(seen) == len(q.labels)


def test_update_stream_edits_all_take_effect():
    g = gen.data_graph(SMALL, 3)
    present = set(map(tuple, g.edges.tolist()))
    for u in gen.update_stream(g, 20, 4, 4, 3):
        add, rem = set(map(tuple, u.add.tolist())), set(map(tuple, u.remove.tolist()))
        assert len(add) == 4 and len(rem) == 4
        assert not add & present and rem <= present
        assert all(a < b for a, b in add | rem)
        present = (present - rem) | add


# ------------------------------------------------------------- reference ----
def _brute_force(n, labels, edges, q_labels, q_edges):
    adj = {(min(u, v), max(u, v)) for u, v in edges}
    out = set()
    for m in itertools.permutations(range(n), len(q_labels)):
        if all(labels[m[i]] == q_labels[i] for i in range(len(q_labels))) and all(
            (min(m[a], m[b]), max(m[a], m[b])) in adj for a, b in q_edges
        ):
            out.add(m)
    return out


def test_reference_equals_brute_force_and_applies_updates():
    rng = np.random.default_rng(0)
    n = 9
    labels = rng.integers(0, 2, n)
    edges = gen.unique_edges(n, rng.integers(0, n, (20, 2)))
    ref = RefGraph(n, labels, edges)
    q_labels, q_edges = labels[[0, 1, 2]], np.array([[0, 1], [1, 2]])
    assert ref.match(q_labels, q_edges) == _brute_force(n, labels, edges.tolist(), q_labels,
                                                        q_edges.tolist())
    add = np.array([[0, 8]]) if 8 not in ref.adj[0] else np.zeros((0, 2), int)
    rem = edges[:2]
    ref.apply(add, rem)
    after = (set(map(tuple, edges.tolist())) - set(map(tuple, rem.tolist()))) | set(
        map(tuple, add.tolist()))
    assert ref.edge_set() == after
    assert ref.match(q_labels, q_edges) == _brute_force(n, labels, sorted(after), q_labels,
                                                        q_edges.tolist())


# ------------------------------------------------------- trace reduction ----
def _ev(plane, line, name, start, dur):
    return {"plane": plane, "line": line, "name": name, "start_ns": float(start),
            "dur_ns": float(dur)}


def test_reduce_hand_trace():
    dev = "/device:TPU:0"
    events = [
        _ev("/host:CPU", "python", "bench.window", 0, 100),
        _ev("/host:spans", "spans", "bench.query_tick", 0, 50),
        _ev("/host:spans", "spans", "stage.join", 15, 30),
        _ev(dev, "XLA Ops", "fusion.1", 10, 20),   # 10..30
        _ev(dev, "XLA Ops", "fusion.2", 20, 20),   # 20..40, overlaps
        _ev(dev, "XLA Ops", "fusion.1", 60, 10),   # 60..70
        _ev(dev, "XLA Ops", "fusion.3", 95, 10),   # 95..105, clipped to 100
        _ev(dev, "XLA Modules", "jit_pairs(1)", 10, 30),
    ]
    r = tracefile.reduce(events)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx((30 + 10 + 5) * 1e-9)  # [10,40] [60,70] [95,100]
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(30e-9)]
    assert dict((k, v) for k, v in r["device_ops"]) == pytest.approx(
        {"fusion.1": 30e-9, "fusion.2": 20e-9, "fusion.3": 5e-9})
    # gaps [0,10] [40,60] [70,95] lie under 0.1 ms: lumped, not labelled
    assert sorted(d for _, d in r["idle_gaps"]) == pytest.approx([10e-9, 20e-9, 25e-9])
    scaled = [dict(e, start_ns=e["start_ns"] * 1e4, dur_ns=e["dur_ns"] * 1e4) for e in events]
    gaps = dict((round(d * 1e9 / 1e4), lab) for lab, d in tracefile.reduce(scaled)["idle_gaps"])
    assert gaps == {10: "bench.query_tick", 20: "bench.query_tick", 25: "no host span open"}
    assert tracefile.device_time_in(scaled, [(15e4, 50e4)]) == pytest.approx(
        (15 + 20) * 1e4 * 1e-9)  # fusion.1 15..30 + fusion.2 20..40
    assert tracefile.op_seconds(r, lambda n: n.startswith("fusion.1")) == pytest.approx(30e-9)
    assert tracefile.op_seconds(r, lambda n: n.startswith("jit_pairs"), "module_ns") == (
        pytest.approx(30e-9))
    assert tracefile.reduce([e for e in events if not e["plane"].startswith("/device")]) is None


def test_reduce_recorded_v5e_trace():
    """A slice of a trace recorded on one v5e chip by a traced run of
    nws100k.read: the reduction against a direct count of the same events."""
    with open(DATA / "trace_v5e.json") as f:
        events = json.load(f)
    r = tracefile.reduce(events)
    w = next(e for e in events if e["name"] == tracefile.WINDOW)
    ops = [e for e in events if e["plane"].startswith("/device:") and e["line"] == "XLA Ops"]
    # busy by a direct sweep over a 1 µs grid
    grid = np.zeros(int(w["dur_ns"] // 1000) + 1, bool)
    for e in ops:
        a = max(int((e["start_ns"] - w["start_ns"]) // 1000), 0)
        b = min(int(np.ceil((e["start_ns"] + e["dur_ns"] - w["start_ns"]) / 1000)), grid.size)
        grid[a:b] = True
    assert r["busy_s"] == pytest.approx(grid.sum() * 1e-6, rel=0.02, abs=5e-5)
    assert 0 < r["busy_s"] < r["window_s"]
    totals = {}
    for e in ops:
        totals[e["name"]] = totals.get(e["name"], 0.0) + e["dur_ns"]
    top = max(totals, key=totals.get)
    assert r["device_ops"][0][0] == top
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    assert sum(r["idle_by_label"].values()) == pytest.approx(r["window_s"] - r["busy_s"])


# ------------------------------------------------------------- roofline ----
def test_leaf_scan_bytes_hand_count():
    # l=2, d=2, n=2: dominance rows 3·2·3 = 18 float32 (72 B), label rows
    # 3·2 = 6 float32 (24 B), path vertices 3 int32 (12 B): 108 B a pair
    assert roofline.leaf_scan_bytes_per_pair(2, 2, 2) == 108
    assert roofline.leaf_scan_bytes(1000, 2, 2, 2) == 108_000
    peaks = cellmod.load_peaks()["TPU v5 lite"]
    assert roofline.roofline_share(819e9, 2.0, peaks) == pytest.approx(50.0)
    assert roofline.roofline_share(0, 2.0, peaks) is None


# ------------------------------------------------------ cells by name ----
def test_every_workload_resolves_to_its_files():
    spec = cellmod.load_spec()
    peaks = cellmod.load_peaks()
    assert all("source" in p for p in peaks.values())
    for w in spec["workloads"]:
        cell = cellmod.resolve(spec, w["name"])
        assert cell.rate is not None and cell.rate > 0
        assert {"vertices", "engine", "guarantees", "assumed"} <= set(cell.config)
        cfg = next(c for c in spec["configs"] if c["name"] == w["config"])
        assert set(cfg["reduced"]) == set(cell.config["reduced"])
        names = {m["name"] for m in cell.end_to_end + cell.per_layer}
        assert "setup_s" in names and len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert any(e["name"] == m["moves"] for e in cell.end_to_end)
        for name in names:
            assert callable(cellmod.reader(name))


def test_a_cell_a_mix_and_a_metric_need_only_new_files(tmp_path):
    """Entries and new files alone add a cell on an existing mix (the
    write mix on nws100k), a new mix and a new per-layer metric; a cell
    runs only once its own file holds a measured rate."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": "nws100k.write", "config": "nws100k", "traffic": "write",
                              "chips": 1, "why": "writes on a large stacked index"})
    burst = json.loads((tmp_path / "bench/traffic/read.json").read_text())
    burst.update(name="zz_burst")
    (tmp_path / "bench/traffic/zz_burst.json").write_text(json.dumps(burst))
    spec["workloads"].append({"name": "nws100k.zz_burst", "config": "nws100k",
                              "traffic": "zz_burst", "chips": 1, "why": "throwaway"})
    (tmp_path / "bench/cells/nws100k.zz_burst.json").write_text('{"rate_ops_per_s": 5.0}')
    (tmp_path / "bench/metrics/zz_ticks.py").write_text(
        "def read(run):\n    return float(len(run.query_ticks))\n")
    spec["per_layer"].append({"name": "zz_ticks", "unit": "ticks", "better": "lower",
                              "source": "program_counter", "layer": "inner executor",
                              "moves": "match_p50_ms", "workloads": ["nws100k.zz_burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    spec = cellmod.load_spec(tmp_path)
    dw = cellmod.resolve(spec, "nws100k.write", root=tmp_path)
    assert dw.traffic["update_share"] > 0 and dw.rate is None
    with pytest.raises(ValueError, match="no offered rate"):
        cellmod.run_cell(dw, 1, 1.0, False, time.perf_counter(), log=lambda *_: None)
    (tmp_path / "bench/cells/nws100k.write.json").write_text('{"rate_ops_per_s": 7.5}')
    assert cellmod.resolve(spec, "nws100k.write", root=tmp_path).rate == 7.5
    assert "update_visible_p95_ms" not in {m["name"] for m in dw.end_to_end}
    zz = cellmod.resolve(spec, "nws100k.zz_burst", root=tmp_path)
    assert zz.rate == 5.0
    assert [m["name"] for m in zz.per_layer][-1] == "zz_ticks"
    # no file that was there has changed
    assert all(p.read_bytes() == b for p, b in before.items() if p.name != "BENCHMARK.json")
    run = cellmod.Run(zz, 1, 1.0, {}, query_ticks=[(0, 1, 0, [1])])
    assert cellmod.reader("zz_ticks", root=tmp_path)(run) == 1.0


# --------------------------------------------------------- whole runs ----
def _env_without_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = str(ROOT / "src")
    return env


def test_run_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nws100k.read", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=_env_without_chip(), capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0
    assert "{" not in p.stdout and "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "nws100k.read", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert p.returncode != 0 and "{" not in p.stdout


WRITE = {"name": "nws100k.write", "config": "nws100k", "traffic": "write", "chips": 1,
         "why": "writes on a large stacked index"}
UPDATE_METRICS = [
    {"name": "update_visible_p95_ms", "unit": "ms", "better": "lower", "bound": 0.25,
     "source": "host_clock", "workloads": ["nws100k.write"]},
]


def _spec_with_writes() -> dict:
    """The committed spec plus a write cell on the write mix in the tree
    (its entry waits for a measured rate)."""
    spec = cellmod.load_spec()
    spec["workloads"].append(WRITE)
    spec["end_to_end"].extend(UPDATE_METRICS)
    return spec


def _tiny(workload: str):
    cell = cellmod.resolve(_spec_with_writes(), workload)
    cell = copy.deepcopy(cell)
    cell.config.update(vertices=400, labels=min(cell.config["labels"], 6))
    cell.config["engine"]["vertices_per_partition"] = 200
    cell.rate = 10.0
    cell.traffic["pool"] = [{"size": 4, "count": 3, "density": "any"},
                            {"size": 8, "count": 1, "density": "dense"},
                            {"size": 8, "count": 1, "density": "sparse"}]
    return cell


def _tiny_run(workload: str):
    return cellmod.run_cell(_tiny(workload), 2**32 + 5, 1.5, False, time.perf_counter(),
                            log=lambda *_: None)


@pytest.mark.parametrize("workload", ["nws100k.read", "nws100k.write"])
def test_tiny_run_is_correct(workload):
    run, checks = _tiny_run(workload)
    assert cellmod.correct(checks), checks
    assert run.requests and all(r.response.status == "ok" for r in run.requests)
    lat = run.match_latencies_s()
    assert len(lat) == len(run.requests) and min(lat) > 0
    if workload == "nws100k.write":
        assert run.update_due and all(0 < v < 60 for v in run.update_visible_s())
        assert checks["edge_diff"]["value"] == 0
        assert cellmod.reader("update_visible_p95_ms")(run) > 0


def _state_unchanged(monkeypatch):
    from repro.core import GnnPeEngine

    monkeypatch.setattr(GnnPeEngine, "apply_updates",
                        lambda self, updates, **kw: {"epoch": self.epoch})


def _half_batch_left_out(monkeypatch):
    from repro.core import GnnPeEngine

    match_many = GnnPeEngine.match_many

    def half(self, queries, *a, **kw):
        out = match_many(self, queries, *a, **kw)
        keep = len(out) // 2
        return out[:keep] + [[] for _ in out[keep:]]

    monkeypatch.setattr(GnnPeEngine, "match_many", half)


def _answer_altered(monkeypatch):
    from repro.core import GnnPeEngine

    match_many = GnnPeEngine.match_many

    def altered(self, queries, *a, **kw):
        out = [list(m) for m in match_many(self, queries, *a, **kw)]
        if out and out[0]:
            first = list(out[0][0])
            first[0] = (int(first[0]) + 1) % self.graph.n_vertices
            out[0][0] = tuple(first)
        return out

    monkeypatch.setattr(GnnPeEngine, "match_many", altered)


def _limit_k_control(monkeypatch):
    sys.path.insert(0, str(BENCH))
    import control

    from repro.core import GnnPeEngine

    monkeypatch.setattr(GnnPeEngine, "match_many", GnnPeEngine.match_many)
    control.limit_answers()


@pytest.mark.parametrize("workload,fault", [
    ("nws100k.write", _state_unchanged),
    ("nws100k.read", _half_batch_left_out),
    ("nws100k.read", _answer_altered),
    ("nws100k.read", _limit_k_control),
], ids=["state-unchanged", "half-batch", "answer-altered", "limit-k-control"])
def test_a_broken_path_comes_out_not_correct(monkeypatch, workload, fault):
    fault(monkeypatch)
    _, checks = _tiny_run(workload)
    assert not cellmod.correct(checks), checks
