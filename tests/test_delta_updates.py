"""Live-graph serving (core/delta.py + serve/cache.py): any sequence of
online inserts/deletes + compactions must match a from-scratch index
rebuild exactly (both index kinds × both probe impls), the stacked
probe must re-stack only compacted slots, the result cache must never
serve a stale entry, and the MatchServer must interleave update ticks
with query ticks."""
import dataclasses

import numpy as np
import pytest

from repro.core import (
    GnnPeConfig,
    GnnPeEngine,
    GraphUpdate,
    apply_graph_update,
    vf2_match,
)
from repro.core.delta import l_hop_reach, paths_touching
from repro.graphs import erdos_renyi, from_edge_list, random_connected_query
from repro.serve.cache import ResultCache
from repro.serve.match_server import MatchServeConfig, MatchServer


def _base_graph(seed: int = 5):
    return erdos_renyi(150, avg_degree=3.5, n_labels=4, seed=seed)


def _engines(g, **overrides):
    """Two identical builds of one config: the delta engine and the
    rebuild-strategy reference (seeded training ⇒ identical params)."""
    cfg = GnnPeConfig(
        n_partitions=3, encoder="monotone", n_multi=1, block_size=32,
        group_size=4, **overrides,
    )
    return GnnPeEngine(cfg).build(g), GnnPeEngine(cfg).build(g)


def _rand_update(rng, g, add=2, remove=2, add_vertices=0, remove_vertices=0):
    e = g.edge_array()
    kwargs = {}
    if remove and e.shape[0] > remove:
        kwargs["remove_edges"] = e[rng.choice(e.shape[0], size=remove, replace=False)]
    if add:
        kwargs["add_edges"] = rng.integers(0, g.n_vertices, size=(add, 2))
    if add_vertices:
        kwargs["add_vertex_labels"] = rng.integers(0, 4, size=add_vertices).astype(np.int32)
    if remove_vertices:
        kwargs["remove_vertices"] = rng.integers(0, g.n_vertices, size=remove_vertices)
    return GraphUpdate(**kwargs)


def _queries(g, n=3, seed0=50):
    out = []
    for s in range(n):
        try:
            out.append(random_connected_query(g, 4 + s % 3, seed=seed0 + s))
        except RuntimeError:
            continue
    assert out
    return out


# ------------------------------------------------------- graph updates ----


def test_apply_graph_update_semantics():
    g = from_edge_list(4, [(0, 1), (1, 2), (2, 3)], np.array([0, 1, 2, 1]))
    # no-op edits touch nothing
    g2, touched = apply_graph_update(g, GraphUpdate(
        add_edges=np.array([[0, 1]]), remove_edges=np.array([[0, 3]])
    ))
    assert touched.size == 0 and g2.n_edges == g.n_edges
    # effective add/remove touch exactly the changed endpoints
    g3, touched = apply_graph_update(g, GraphUpdate(
        add_edges=np.array([[0, 3]]), remove_edges=np.array([[1, 2]])
    ))
    assert sorted(touched.tolist()) == [0, 1, 2, 3]
    assert g3.has_edge(0, 3) and not g3.has_edge(1, 2)
    # vertex append + removal: ids never renumber, removal isolates
    g4, touched = apply_graph_update(g, GraphUpdate(
        add_vertex_labels=np.array([3], np.int32),
        add_edges=np.array([[4, 0]]),
        remove_vertices=np.array([2]),
    ))
    assert g4.n_vertices == 5
    assert g4.has_edge(4, 0)
    assert g4.neighbors(2).size == 0  # isolated zombie
    assert {0, 1, 2, 3, 4} >= set(touched.tolist()) >= {0, 2, 4}


def test_l_hop_reach_and_paths_touching():
    g = from_edge_list(6, [(0, 1), (1, 2), (2, 3), (3, 4)], np.zeros(6, np.int32))
    assert l_hop_reach(g, np.array([2]), 1).tolist() == [1, 2, 3]
    assert l_hop_reach(g, np.array([2]), 2).tolist() == [0, 1, 2, 3, 4]
    paths = np.array([[0, 1, 2], [3, 4, 3], [5, 5, 5]], np.int32)
    assert paths_touching(paths, np.array([2, 4])).tolist() == [True, True, False]


# ---------------------------------------------- delta ≡ rebuild property ----


@pytest.mark.parametrize(
    "kind,quantize,plan_weight",
    [
        ("path", False, "dr"),
        ("path", True, "deg"),
        ("grouped", True, "dr"),
        ("grouped", False, "deg"),
    ],
)
def test_delta_equals_rebuild_property(kind, quantize, plan_weight):
    """Random insert/delete/vertex sequences + forced compactions: the
    delta engine's matches equal the from-scratch rebuild's at EVERY
    epoch (and VF2's), for both index kinds, with and without the int8
    sidecar, under both plan weights."""
    g = _base_graph()
    # epoch 2 compacts (tiny threshold engaged via needs_compaction math):
    # run half the epochs with compaction off, half with it forced on
    eng_d, eng_r = _engines(
        g, index_kind=kind, quantize_index=quantize,
        plan_weight=plan_weight, delta_compact_min=10**9,
    )
    rng = np.random.default_rng(hash((kind, quantize, plan_weight)) % 2**32)
    queries = _queries(g)
    for epoch in range(4):
        if epoch == 2:
            # force compaction pressure from now on
            eng_d.cfg = dataclasses.replace(
                eng_d.cfg, delta_compact_min=8, delta_compact_frac=0.01
            )
        upd = _rand_update(
            rng, eng_d.graph,
            add_vertices=1 if epoch == 1 else 0,
            remove_vertices=1 if epoch == 3 else 0,
        )
        s = eng_d.apply_updates(upd)
        assert s["epoch"] == epoch + 1
        eng_r.apply_updates(upd, strategy="rebuild")
        cur = eng_d.graph
        md = eng_d.match_many(queries)
        mr = eng_r.match_many(queries)
        for qi, q in enumerate(queries):
            assert sorted(md[qi]) == sorted(mr[qi]), (
                f"{kind}/{quantize}/{plan_weight} epoch {epoch} q{qi}: delta != rebuild"
            )
            assert set(md[qi]) == set(vf2_match(cur, q)), f"q{qi}: != VF2 oracle"
        if epoch >= 2:
            assert s["compacted"], "forced compaction threshold did not trigger"
    # a batch of one agrees with the batch and with VF2 under pending deltas
    ms = eng_d.match(queries[0])
    assert sorted(ms) == sorted(md[0])
    assert set(ms) == set(vf2_match(eng_d.graph, queries[0]))


def test_delta_buffers_probed_without_compaction():
    """With compaction disabled, candidates really come from the
    main ∪ delta − tombstones decomposition (buffer stays populated)."""
    g = _base_graph()
    eng_d, eng_r = _engines(g, delta_compact_min=10**9)
    rng = np.random.default_rng(7)
    queries = _queries(g)
    for _ in range(3):
        upd = _rand_update(rng, eng_d.graph, add=3, remove=3)
        eng_d.apply_updates(upd)
        eng_r.apply_updates(upd, strategy="rebuild")
    stats = eng_d.delta.stats()
    assert stats["delta_rows"] > 0 and stats["tombstones"] > 0
    assert stats["n_compactions"] == 0
    md = eng_d.match_many(queries)
    mr = eng_r.match_many(queries)
    for a, b in zip(md, mr):
        assert sorted(a) == sorted(b)


def test_elastic_restack_only_touches_compacted_slot():
    """Compaction under a live stacked probe rewrites ONLY the affected
    shard slot (the probe object survives) and padding stats stay
    consistent; results stay equal to VF2's."""
    g = _base_graph()
    eng, _ = _engines(
        g, index_kind="grouped", quantize_index=True,
        delta_compact_min=8, delta_compact_frac=0.01,
    )
    probe = eng._stacked_probe
    assert probe is not None
    rng = np.random.default_rng(3)
    queries = _queries(g)
    compacted_any = False
    for _ in range(3):
        s = eng.apply_updates(_rand_update(rng, eng.graph, add=3, remove=3))
        compacted_any |= bool(s["compacted"])
        if eng._stacked_probe is not None:
            assert eng._stacked_probe is probe, "full restack instead of elastic slot update"
            st = eng._stacked_probe.stacked
            assert st.nbytes() == st.padding_stats()["stacked_bytes"]
            assert int(st.n_paths[st.slot_of].sum()) == sum(
                m.index.n_paths for m in eng.models
            )
        stacked = eng.match_many(queries)
        for q, a in zip(queries, stacked):
            assert set(a) == set(vf2_match(eng.graph, q))
    assert compacted_any


def test_stacked_leaf_pair_cap_identical_results():
    """The capacity-bounded (chunked) leaf member-expansion returns the
    same rows as the unbounded expansion."""
    g = _base_graph()
    cfg = dict(index_kind="grouped", quantize_index=True)
    eng, _ = _engines(g, **cfg)
    queries = _queries(g, n=4)
    big = eng.match_many(queries)
    # rebuild the probe with a pathologically small cap → many chunks
    eng.cfg = dataclasses.replace(eng.cfg, stacked_leaf_pair_cap=64)
    eng._stacked_probe = None
    small = eng.match_many(queries)
    assert eng.stacked_probe().leaf_pair_cap == 64
    for a, b in zip(big, small):
        assert a == b


# ----------------------------------------------------------- result cache ----


def test_result_cache_hits_and_isomorphic_remap():
    g = _base_graph()
    cfg = GnnPeConfig(n_partitions=3, encoder="monotone", n_multi=1, block_size=32, cache=True)
    eng = GnnPeEngine(cfg).build(g)
    q = _queries(g)[0]
    m1 = eng.match(q)
    m2 = eng.match(q)
    assert m1 == m2
    st = eng._result_cache.stats
    assert (st.hits, st.misses) == (1, 1)
    # relabeled-isomorphic query: hit + exact remap through its own perm
    rng = np.random.default_rng(3)
    perm = rng.permutation(q.n_vertices)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(q.n_vertices)
    q_iso = from_edge_list(
        q.n_vertices,
        [(int(inv[u]), int(inv[v])) for u, v in q.edge_array()],
        q.labels[perm],
    )
    m_iso = eng.match(q_iso)
    assert eng._result_cache.stats.hits == 2
    assert set(m_iso) == set(vf2_match(g, q_iso))
    # cached stats flag — and a usable plan (quickstart prints plan.n_paths)
    _, stats = eng.match(q, return_stats=True)
    assert stats.cache_hit and stats.n_matches == len(m1)
    assert stats.plan is not None and stats.plan.n_paths >= 1
    covered = {v for p in stats.plan.paths for v in p}
    assert covered == set(range(q.n_vertices))  # remapped into THIS query's ids


def test_result_cache_never_stale_under_updates():
    """Serve → update → serve: every answer equals the VF2 oracle on the
    live graph, including updates that make a previously zero-candidate
    partition start contributing (the label-hash invalidation rule)."""
    g = _base_graph()
    cfg = GnnPeConfig(
        n_partitions=3, encoder="monotone", n_multi=1, block_size=32,
        cache=True, delta_compact_min=10**9,
    )
    eng = GnnPeEngine(cfg).build(g)
    rng = np.random.default_rng(11)
    queries = _queries(g, n=3)
    for epoch in range(4):
        for q in queries + queries:  # repeat inside the epoch → cache hits
            got = eng.match(q)
            assert set(got) == set(vf2_match(eng.graph, q)), f"stale at epoch {epoch}"
        eng.apply_updates(_rand_update(rng, eng.graph, add=3, remove=3))
    assert eng._result_cache.stats.hits >= 4  # repeats actually hit


def test_result_cache_partition_scoped_invalidation_unit():
    cache = ResultCache(capacity=8)
    m = np.zeros((1, 3), np.int32)
    cache.put(b"a", m, contributing={0}, plan_hashes={101}, epoch=0)
    cache.put(b"b", m, contributing={1}, plan_hashes={202}, epoch=0)
    # deletion in partition 0 evicts only its contributor
    cache.invalidate({0: {"deleted": True, "inserted_hashes": np.zeros(0, np.int64)}})
    assert cache.get(b"a") is None and cache.get(b"b") is not None
    # insertion into a NON-contributing partition evicts only entries whose
    # plan-path label hashes collide with the new paths'
    cache.put(b"c", m, contributing={1}, plan_hashes={303}, epoch=1)
    cache.invalidate({2: {"deleted": False, "inserted_hashes": np.asarray([303])}})
    assert cache.get(b"c") is None
    assert cache.get(b"b") is not None  # hash 202 untouched
    # capacity LRU
    small = ResultCache(capacity=2)
    for i, key in enumerate([b"x", b"y", b"z"]):
        small.put(key, m, contributing={0}, plan_hashes={i}, epoch=0)
    assert small.get(b"x") is None and small.get(b"z") is not None
    assert small.stats.evicted == 1


def test_zero_contribution_partition_gains_matches():
    """A cached EMPTY result must be invalidated when an update inserts
    label-compatible paths into a partition that contributed nothing."""
    # path graph with a unique label pattern only matchable after the update
    # (one lone label-1 vertex keeps label 1 in the frozen vocabulary
    # without enabling any 1-1-1 chain)
    n = 40
    edges = [(i, i + 1) for i in range(n - 1)]
    labels = np.zeros(n, np.int32)
    labels[n - 1] = 1
    g = from_edge_list(n, edges, labels)
    cfg = GnnPeConfig(
        n_partitions=2, encoder="monotone", n_multi=0, block_size=32,
        cache=True, delta_compact_min=10**9, theta=10,
    )
    eng = GnnPeEngine(cfg).build(g)
    # query: a 3-chain labeled 1-1-1 — zero matches anywhere initially
    q = from_edge_list(3, [(0, 1), (1, 2)], np.array([1, 1, 1], np.int32))
    assert eng.match(q) == []
    assert eng.match(q) == []  # cached empty result
    assert eng._result_cache.stats.hits == 1
    # append three label-1 vertices wired into the graph → one new match
    upd = GraphUpdate(
        add_vertex_labels=np.array([1, 1, 1], np.int32),
        add_edges=np.array([[n, n + 1], [n + 1, n + 2], [0, n]]),
    )
    eng.apply_updates(upd)
    got = eng.match(q)
    oracle = vf2_match(eng.graph, q)
    assert len(oracle) > 0, "update should have created matches"
    assert set(got) == set(oracle), "stale empty result served from cache"


# ------------------------------------------------------------ dr plan cache ----


def test_dr_plan_cache_reuses_and_retires_on_update():
    g = _base_graph()
    cfg = GnnPeConfig(
        n_partitions=2, encoder="monotone", n_multi=0, block_size=32,
        plan_weight="dr",
    )
    eng = GnnPeEngine(cfg).build(g)
    q = _queries(g)[0]
    eng.match(q)
    fp = eng._emb_fingerprint
    assert eng._dr_plan_peek(q, 1) is not None, "dr plan not cached"
    plan_a = eng._dr_plan_peek(q, 1)
    m_a = eng.match(q)  # served with the cached plan
    assert set(m_a) == set(vf2_match(eng.graph, q))
    # an update changes the embedding fingerprint → cached dr plan retires
    e = eng.graph.edge_array()
    eng.apply_updates(GraphUpdate(remove_edges=e[:1]))
    assert eng._emb_fingerprint != fp
    assert eng._dr_plan_peek(q, 1) is None
    m_b = eng.match(q)
    assert set(m_b) == set(vf2_match(eng.graph, q))
    assert plan_a is not None


# ------------------------------------------------------------- match server ----


def test_match_server_interleaves_update_ticks():
    g = _base_graph()
    cfg = GnnPeConfig(
        n_partitions=3, encoder="monotone", n_multi=1, block_size=32, cache=True,
    )
    eng = GnnPeEngine(cfg).build(g)
    server = MatchServer(eng, MatchServeConfig(max_batch=4, max_updates_per_tick=2))
    rng = np.random.default_rng(9)
    queries = _queries(g, n=3)
    rids_pre = [server.submit(q) for q in queries]
    server.run_until_drained()
    server.submit_update(_rand_update(rng, eng.graph, add=2, remove=2))
    server.submit_update(_rand_update(rng, eng.graph, add=2, remove=0))
    rids_post = [server.submit(q) for q in queries]
    server.run_until_drained()
    assert server.n_updates_applied == 2
    assert eng.epoch == 1  # both coalesced into one tick/epoch
    for rid, q in zip(rids_post, queries):
        assert set(server.finished[rid]) == set(vf2_match(eng.graph, q))
    assert len(server.update_summaries) == 1
    assert rids_pre[0] in server.finished
