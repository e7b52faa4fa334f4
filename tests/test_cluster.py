"""Multi-host cluster tier (dist/cluster.py + dist/placement.py +
serve/cache.py ShardedResultCache + serve/router.py): scatter-gather
``match_many`` must be byte-identical to the single-process engine at
every delta epoch across index kinds, probe impls and host counts;
cost-ranked placement must respect the LPT Graham bound on skewed
costs; a host lost mid-gather must be re-probed locally without
changing matches; partition-local update streams must evict only the
owner host's cache shard; blue-green generation installs must be
version-checked; and a real 2-process run over the DirExchange data
plane (with the ``jax.distributed`` bootstrap) must agree with local
``match_many``."""
import os
import subprocess
import sys
import tempfile
import textwrap
import threading

import numpy as np
import pytest

from repro.core import GnnPeConfig, GnnPeEngine, GraphUpdate
from repro.core.delta import touch_hint
from repro.dist.checkpoint import CheckpointManager
from repro.dist.cluster import (
    ClusterEngine,
    DirExchange,
    ExchangeHost,
    HostLostError,
    LocalHost,
    init_distributed,
    serve_exchange_host,
)
from repro.dist.placement import (
    PartitionCost,
    Placement,
    partition_costs,
    place_partitions,
)
from repro.graphs import erdos_renyi, random_connected_query
from repro.serve.match_server import MatchServeConfig, MatchServer
from repro.serve.router import ClusterRouter


def _base_graph(seed: int = 5):
    return erdos_renyi(150, avg_degree=3.5, n_labels=4, seed=seed)


def _engine(g, **overrides):
    cfg = GnnPeConfig(
        n_partitions=3, encoder="monotone", n_multi=1, block_size=32,
        group_size=4, seed=7, **overrides,
    )
    return GnnPeEngine(cfg).build(g)


def _rand_update(rng, g, add=3, remove=2):
    e = g.edge_array()
    kwargs = {"add_edges": rng.integers(0, g.n_vertices, size=(add, 2))}
    if remove and e.shape[0] > remove:
        kwargs["remove_edges"] = e[rng.choice(e.shape[0], size=remove, replace=False)]
    return GraphUpdate(**kwargs)


def _queries(g, n=4, seed0=50):
    out = []
    for s in range(n):
        try:
            out.append(random_connected_query(g, 4 + s % 3, seed=seed0 + s))
        except RuntimeError:
            continue
    assert out
    return out


def _sorted(matches):
    return sorted(matches)


# ----------------------------------------------------------- placement ----


def test_placement_respects_graham_bound_on_skewed_costs():
    """LPT property test: max host load ≤ total/n + max single cost,
    on adversarially skewed cost distributions."""
    rng = np.random.default_rng(0)
    for trial in range(25):
        n_parts = int(rng.integers(1, 40))
        n_hosts = int(rng.integers(1, 9))
        kind = trial % 3
        if kind == 0:  # power-law skew
            vals = (1000.0 / (1 + np.arange(n_parts))) ** 2
        elif kind == 1:  # one giant, many tiny
            vals = np.ones(n_parts)
            vals[0] = 1e6
        else:
            vals = rng.uniform(0.0, 100.0, n_parts)
        costs = [PartitionCost(part_id=i, cost=float(v)) for i, v in enumerate(vals)]
        p = place_partitions(costs, n_hosts)
        assert p.balanced(), (trial, p.max_load(), p.bound)
        # every partition owned exactly once
        assert sorted(sum((p.owned(h) for h in range(n_hosts)), [])) == list(range(n_parts))


def test_placement_deterministic_and_cold_start_defined():
    costs = [PartitionCost(part_id=i, cost=0.0, nbytes=100 - i) for i in range(4)]
    a = place_partitions(partition_costs([{"part_id": i, "rows": 0} for i in range(4)]), 2)
    b = place_partitions(partition_costs([{"part_id": i, "rows": 0} for i in range(4)]), 2)
    assert np.array_equal(a.host_of, b.host_of)
    p = place_partitions(costs, 8)  # more hosts than partitions
    assert p.balanced() and len(sum((p.owned(h) for h in range(8)), [])) == 4


def test_partition_stats_surface():
    """The stable placement-signal API: one record per partition with
    the documented keys; probe-work counters populate under the stacked
    impl and feed a placement that separates hot partitions."""
    g = _base_graph()
    eng = _engine(g)
    stats = eng.partition_stats()
    assert len(stats) == len(eng.models)
    for s in stats:
        assert {"part_id", "rows", "nbytes", "leaf_pairs", "probe_rows",
                "delta_rows", "tombstones"} <= set(s)
        assert s["rows"] > 0 and s["nbytes"] > 0
    eng.match_many(_queries(g))
    stats = eng.partition_stats()
    assert sum(s["leaf_pairs"] for s in stats) > 0
    costs = partition_costs(stats)
    assert any(c.cost > 0 for c in costs)


# ------------------------------------------------- scatter-gather identity ----


@pytest.mark.parametrize(
    "overrides",
    [
        dict(index_kind="grouped", plan_weight="deg", join_impl="device"),
        dict(index_kind="path", plan_weight="dr"),
        dict(index_kind="grouped", plan_weight="dr"),
        dict(index_kind="path", plan_weight="deg", join_impl="device"),
    ],
)
def test_cluster_matches_identical_to_single_process(overrides):
    """The tier's identity contract: cluster ``match_many`` equals the
    single-process engine byte for byte, for every host count, at every
    delta epoch (main + delta + tombstones all cross the scatter)."""
    g = _base_graph()
    eng = _engine(g, **overrides)
    queries = _queries(g)
    rng = np.random.default_rng(3)
    for n_hosts in (1, 2, 4):
        cl = ClusterEngine(eng, n_hosts=n_hosts)
        for _ in range(3):
            assert cl.match_many(queries) == eng.match_many(queries), (n_hosts,)
            cl.apply_updates(_rand_update(rng, eng.graph))
        # placement stays within the Graham bound once probe counters exist
        assert cl.rebalance().balanced()


def test_cluster_host_loss_rescatters_locally():
    g = _base_graph()
    eng = _engine(g)
    queries = _queries(g)
    cl = ClusterEngine(eng, n_hosts=3)
    cl.apply_updates(_rand_update(np.random.default_rng(9), eng.graph))
    for h in cl.hosts:
        h.fail_next = True
    assert cl.match_many(queries) == eng.match_many(queries)
    assert cl.stats["host_losses"] >= 1
    # losses are transient: the next round probes the hosts again
    assert cl.match_many(queries) == eng.match_many(queries)


# -------------------------------------------------------- sharded cache ----


def test_sharded_cache_locality_partition_local_stream():
    """An update confined to one partition's vertex region must evict
    only on that partition's owner shard: remote_evictions == 0 on a
    collision-free partition-local stream."""
    g = _base_graph()
    eng = _engine(g)
    queries = _queries(g, n=6)
    cl = ClusterEngine(eng, n_hosts=3, cache_capacity=64)
    first = cl.match_many(queries)
    assert cl.match_many(queries) == first  # cache hits serve identically
    assert cl.cache.stats.hits >= len(queries)
    # partition-local stream: deletions confined to partition 0's member
    # region (rule 2 cannot fire on deletions, and every evicted entry's
    # home shard owns a mutated partition)
    p0 = set(int(v) for v in eng.models[0].members)
    e = eng.graph.edge_array()
    local_e = np.array(
        [ed for ed in e.tolist() if ed[0] in p0 and ed[1] in p0][:4], np.int64
    )
    assert local_e.size, "fixture graph left partition 0 with no interior edges"
    cl.apply_updates(GraphUpdate(remove_edges=local_e))
    loc = cl.cache.locality()
    assert loc["local_evictions"] > 0, loc  # the update did invalidate
    assert loc["remote_evictions"] == 0, loc  # ...only on owner shards
    # post-invalidation correctness at the new epoch
    assert cl.match_many(queries) == eng.match_many(queries)


def test_sharded_cache_homing_and_placement():
    from repro.serve.cache import ShardedResultCache

    c = ShardedResultCache(3, capacity=8)
    c.set_placement([2, 0, 1])  # partition mi -> host
    m = np.zeros((1, 3), np.int32)
    assert c.put(b"k1", m, {0}, {7}, epoch=0) == 2
    assert c.put(b"k2", m, {1, 2}, {7}, epoch=0) == 0  # min contributing = 1
    assert c.put(b"k3", m, {0, 1}, {7}, epoch=0) == 2  # crosses hosts 2 and 0
    assert c.get(b"k1") is not None and len(c) == 3
    # invalidating partition 1 eagerly evicts k2 on its owner shard only;
    # k3 (homed on host 2's shard) is NOT chased cross-shard...
    n = c.invalidate({1: {"deleted": True, "inserted_hashes": []}})
    assert n == 1 and c.get(b"k2") is None
    assert c.locality()["remote_evictions"] == 0
    # ...but its contributing partition 1 mutated after insertion, so the
    # lazy tick check drops it at get instead of serving stale matches
    assert c.get(b"k3") is None
    assert c.locality()["lazy_evictions"] == 1
    assert c.get(b"k1") is not None  # untouched partition survives both paths


# ----------------------------------------------------------- blue-green ----


def test_blue_green_generation_swap_and_version_check():
    g = _base_graph()
    eng = _engine(g)
    queries = _queries(g)
    cl = ClusterEngine(eng, n_hosts=2)
    rng = np.random.default_rng(5)
    cl.apply_updates(_rand_update(rng, eng.graph))
    before = [_sorted(m) for m in eng.match_many(queries)]
    with tempfile.TemporaryDirectory() as root:
        store = CheckpointManager(root)
        out = cl.rebuild_generation(store=store)
        assert out["installed"]
        assert store.latest_step() == out["generation"]
    # the swap drained deltas and tombstones; matches are unchanged
    assert eng.delta_stats()["delta_rows"] == 0
    assert eng.delta_stats()["tombstones"] == 0
    assert [_sorted(m) for m in cl.match_many(queries)] == before
    # stale install refused: an update lands between snapshot and install
    snap = eng.prepare_generation()
    built = eng.build_generation(snap)
    cl.apply_updates(_rand_update(rng, eng.graph))
    assert eng.install_generation(snap, built) is False
    # the bounded retry loop re-snapshots and succeeds
    assert cl.rebuild_generation()["installed"]
    assert cl.match_many(queries) == eng.match_many(queries)


# ------------------------------------------------------ update coalescing ----


def test_touch_hint_conservative():
    u = GraphUpdate(add_edges=np.array([[1, 2]]), remove_edges=np.array([[3, 4]]),
                    remove_vertices=np.array([5]))
    verts, adds = touch_hint(u)
    assert set(int(v) for v in verts) == {1, 2, 3, 4, 5} and not adds
    _, adds = touch_hint(GraphUpdate(add_vertex_labels=np.array([0], np.int32)))
    assert adds


def test_hot_vertex_coalescing_identical_matches_fewer_epochs():
    """Repeated touches of one vertex inside a tick re-embed its stars
    once: the coalesced run applies the same updates in fewer epochs and
    post-epoch matches are identical."""
    g = _base_graph()
    queries = _queries(g)
    rng = np.random.default_rng(2)
    hub = int(rng.integers(0, g.n_vertices))
    updates = []
    for k in range(10):
        if k % 3 == 2:
            updates.append(GraphUpdate(add_edges=rng.integers(0, g.n_vertices, (2, 2))))
        else:
            o = rng.integers(0, g.n_vertices, (2, 1))
            updates.append(GraphUpdate(
                add_edges=np.concatenate([np.full((2, 1), hub), o], axis=1)))

    def run(coalesce):
        srv = MatchServer(
            _engine(g),
            MatchServeConfig(max_updates_per_tick=1, coalesce_hot=coalesce),
        )
        for u in updates:
            srv.submit_update(u)
        while srv.update_queue:
            srv.apply_update_tick()
        rids = [srv.submit(q) for q in queries]
        srv.run_until_drained()
        return [srv.finished[r] for r in rids], len(srv.update_summaries), srv

    base, epochs_off, _ = run(False)
    got, epochs_on, srv = run(True)
    assert srv.n_updates_applied == len(updates)
    assert epochs_on < epochs_off and srv.coalesced_pulls > 0
    assert [_sorted(a) for a in base] == [_sorted(b) for b in got]


def test_coalescing_never_pulls_past_conflicts_or_vertex_adds():
    srv = MatchServer(
        _engine(_base_graph()),
        MatchServeConfig(max_updates_per_tick=1, coalesce_hot=True),
    )
    hub = GraphUpdate(add_edges=np.array([[0, 1]]))
    conflicted = GraphUpdate(add_edges=np.array([[0, 2]]))  # hot but behind a conflict
    blocker = GraphUpdate(add_edges=np.array([[2, 3]]))  # skipped, shares vertex 2
    adder = GraphUpdate(add_vertex_labels=np.array([0], np.int32))
    behind_adder = GraphUpdate(add_edges=np.array([[0, 4]]))
    for u in (hub, blocker, conflicted, adder, behind_adder):
        srv.submit_update(u)
    srv.apply_update_tick()
    # nothing was pullable: `conflicted` intersects skipped `blocker`,
    # and `behind_adder` sits behind a vertex-appending update
    assert srv.coalesced_pulls == 0
    assert len(srv.update_queue) == 4


# --------------------------------------------------------------- router ----


def test_cluster_router_serves_through_cluster():
    g = _base_graph()
    eng = _engine(g)
    queries = _queries(g)
    cl = ClusterEngine(eng, n_hosts=2, cache_capacity=32)
    rt = ClusterRouter(cl, max_batch=2)
    rng = np.random.default_rng(4)
    updates = [_rand_update(rng, g) for _ in range(2)]
    for u in updates:
        rt.submit_update(u)
    rids = [rt.submit(q) for q in queries]
    rt.run_until_drained()
    ref = _engine(g)
    ref.apply_updates(updates)
    assert [rt.finished[r] for r in rids] == ref.match_many(queries)
    st = rt.stats()
    assert st["n_finished"] == len(queries) and st["placement"]["balanced"]


# ----------------------------------------------------- exchange data plane ----


def test_exchange_host_probe_roundtrip_threaded():
    """DirExchange protocol end to end (worker on a thread): a cluster
    spanning a LocalHost and an ExchangeHost replica agrees with the
    single-process engine, before and after a delta epoch."""
    g = _base_graph()
    eng = _engine(g, index_kind="grouped", plan_weight="dr")
    replica = _engine(g, index_kind="grouped", plan_weight="dr")
    queries = _queries(g)
    with tempfile.TemporaryDirectory() as root:
        ex = DirExchange(root)
        t = threading.Thread(
            target=serve_exchange_host, args=(replica, 1, ex), kwargs={"timeout": 60.0}
        )
        t.start()
        try:
            cl = ClusterEngine(eng, hosts=[LocalHost(0, eng), ExchangeHost(1, ex, timeout=60.0)])
            assert cl.match_many(queries) == eng.match_many(queries)
            up = _rand_update(np.random.default_rng(6), g)
            cl.apply_updates(up)
            replica.apply_updates(up)
            assert cl.match_many(queries) == eng.match_many(queries)
        finally:
            cl.shutdown()
            t.join(timeout=60)
        assert not t.is_alive()


def test_exchange_timeout_is_host_loss():
    with tempfile.TemporaryDirectory() as root:
        ex = DirExchange(root)
        with pytest.raises(HostLostError):
            ex.get("never_written", timeout=0.05, poll=0.01)


def test_init_distributed_local_fallback():
    out = init_distributed(num_processes=1)
    assert out["mode"] == "local"


# ------------------------------------------------- 2-process smoke (CI) ----

_WORKER = textwrap.dedent(
    """
    import sys
    import numpy as np
    from repro.core import GnnPeConfig, GnnPeEngine
    from repro.dist.cluster import DirExchange, init_distributed, serve_exchange_host
    from repro.graphs import erdos_renyi

    root, coord = sys.argv[1], sys.argv[2]
    boot = init_distributed(num_processes=2, process_id=1, coordinator_address=coord)
    g = erdos_renyi(150, avg_degree=3.5, n_labels=4, seed=5)
    cfg = GnnPeConfig(n_partitions=3, encoder="monotone", n_multi=1,
                      block_size=32, group_size=4, seed=7)
    eng = GnnPeEngine(cfg).build(g)
    n = serve_exchange_host(eng, 1, DirExchange(root), timeout=240.0)
    print("WORKER_OK", boot["mode"], n)
    """
)

_COORD = textwrap.dedent(
    """
    import sys
    import numpy as np
    from repro.core import GnnPeConfig, GnnPeEngine
    from repro.dist.cluster import ClusterEngine, DirExchange, ExchangeHost, LocalHost, init_distributed
    from repro.graphs import erdos_renyi, random_connected_query

    root, coord = sys.argv[1], sys.argv[2]
    boot = init_distributed(num_processes=2, process_id=0, coordinator_address=coord)
    g = erdos_renyi(150, avg_degree=3.5, n_labels=4, seed=5)
    cfg = GnnPeConfig(n_partitions=3, encoder="monotone", n_multi=1,
                      block_size=32, group_size=4, seed=7)
    eng = GnnPeEngine(cfg).build(g)
    queries = []
    for s in range(4):
        try:
            queries.append(random_connected_query(g, 4 + s % 3, seed=50 + s))
        except RuntimeError:
            pass
    ex = DirExchange(root)
    cl = ClusterEngine(eng, hosts=[LocalHost(0, eng), ExchangeHost(1, ex, timeout=240.0)])
    assert len(cl.hosts[1].owned) > 0, "placement left the remote host idle"
    got = cl.match_many(queries)
    exp = eng.match_many(queries)
    assert got == exp, "scatter-gather != local match_many"
    cl.shutdown()
    print("COORD_OK", boot["mode"], sum(len(m) for m in got))
    """
)


def test_two_process_cluster_smoke():
    """Real 2-process run: a coordinator and a worker process share only
    the DirExchange directory (plus the jax.distributed coordination
    service when the backend supports it); the scattered match batch
    must equal local ``match_many``."""
    import socket

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coord = f"127.0.0.1:{port}"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([os.path.join(os.path.dirname(__file__), "..", "src")]
                                         + ([os.environ["PYTHONPATH"]] if os.environ.get("PYTHONPATH") else []))}
    with tempfile.TemporaryDirectory() as root:
        worker = subprocess.Popen(
            [sys.executable, "-c", _WORKER, root, coord],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        coordp = subprocess.Popen(
            [sys.executable, "-c", _COORD, root, coord],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        out_c, err_c = coordp.communicate(timeout=600)
        out_w, err_w = worker.communicate(timeout=600)
    assert coordp.returncode == 0, f"coordinator failed:\n{out_c}\n{err_c}"
    assert worker.returncode == 0, f"worker failed:\n{out_w}\n{err_w}"
    assert "COORD_OK" in out_c and "WORKER_OK" in out_w
