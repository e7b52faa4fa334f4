"""Device merge-join subsystem (§device-join PR).

Covers, in rough dependency order:

  * the ``kernels/merge_join`` ops against their NumPy references
    (multi-word key packing, run bounds, run-length expansion, the
    injectivity verdict incl. the Pallas kernel, keyed dedup);
  * ``join_candidates``/``refine``/``match_from_candidates`` in BOTH
    implementations against a brute-force VF2 oracle on random small
    graphs — including the cartesian no-shared-column branch and
    ``induced=True`` non-edge checks, which previously had no direct
    oracle coverage;
  * the int64 overflow guard in the host refine's edge keys;
  * engine-level identity: ``join_impl="device"`` must produce
    ``sort_matches``-identical results across index kinds × probe impls
    × delta epochs, with zero host-side leaf member expansions on the
    stacked path;
  * the per-partition auto group size and the cost-ranked MatchServer
    schedule (this PR's satellites).
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import (
    GnnPeConfig,
    GnnPeEngine,
    GraphUpdate,
    TrainConfig,
    vf2_match,
)
from repro.core.matcher import (
    _edge_key_arrays,
    join_candidates,
    match_from_candidates,
    refine,
    sort_matches,
)
from repro.core.paths import enumerate_paths
from repro.core.planner import plan_query
from repro.graphs import from_edge_list, newman_watts_strogatz, random_connected_query

# ---------------------------------------------------------------------------
# kernels/merge_join ops vs NumPy references
# ---------------------------------------------------------------------------


def test_merge_join_ops_match_refs():
    import jax.numpy as jnp

    from repro.kernels.merge_join import ops as mj
    from repro.kernels.merge_join.ref import (
        dedup_mask_ref,
        expand_pairs_ref,
        injectivity_mask_ref,
        pack_words_ref,
        run_bounds_ref,
    )

    rng = np.random.default_rng(0)
    for _ in range(6):
        C = int(rng.integers(1, 9))
        bits = int(rng.integers(3, 32))
        R = 160
        rows = rng.integers(0, min(2**bits, 10**6), (R, C)).astype(np.int32)
        w_ref = pack_words_ref(rows, bits)
        assert (w_ref == np.asarray(mj.pack_words(jnp.asarray(rows), bits))).all()
        # word-lex order == row-lex order
        o_rows = np.lexsort(tuple(rows[:, j] for j in range(C - 1, -1, -1)))
        o_w = np.asarray(mj.lex_order(jnp.asarray(w_ref)))
        assert (rows[o_rows] == rows[o_w]).all()
        sw = w_ref[np.lexsort(tuple(w_ref[:, k] for k in range(w_ref.shape[1] - 1, -1, -1)))]
        probe = w_ref[rng.integers(0, R, 48)]
        lo_r, hi_r = run_bounds_ref(sw, probe)
        for fn in (mj.run_bounds, mj.run_lookup):
            lo_d, hi_d = fn(jnp.asarray(sw), jnp.asarray(probe))
            assert (lo_r == np.asarray(lo_d)).all() and (hi_r == np.asarray(hi_d)).all()
        cap = 1 << max(int((hi_r - lo_r).sum()) - 1, 1).bit_length()
        r1, c1, v1 = expand_pairs_ref(lo_r, hi_r, cap)
        r2, c2, v2 = mj.expand_pairs(jnp.asarray(lo_r), jnp.asarray(hi_r), cap)
        assert (r1[v1] == np.asarray(r2)[np.asarray(v2)]).all()
        assert (c1[v1] == np.asarray(c2)[np.asarray(v2)]).all()
        old = rng.integers(0, 6, (R, 3)).astype(np.int32)
        new = rng.integers(0, 6, (R, 2)).astype(np.int32)
        i_ref = injectivity_mask_ref(old, new)
        assert (i_ref == np.asarray(mj.injectivity_mask(jnp.asarray(old), jnp.asarray(new)))).all()
        assert (
            i_ref
            == np.asarray(
                mj.injectivity_mask(jnp.asarray(old), jnp.asarray(new), use_pallas=True)
            )
        ).all()
        valid = rng.random(R) > 0.25
        o_r, k_r = dedup_mask_ref(w_ref, valid)
        o_d, k_d = mj.dedup_mask(jnp.asarray(w_ref), jnp.asarray(valid))
        kept_ref = {tuple(x) for x in w_ref[o_r][k_r]}
        kept_dev = {tuple(x) for x in w_ref[np.asarray(o_d)][np.asarray(k_d)]}
        assert kept_ref == kept_dev == {tuple(x) for x in w_ref[valid]}


# ---------------------------------------------------------------------------
# join + refine vs brute-force VF2 oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("induced", [False, True])
def test_join_refine_vs_vf2_oracle(induced):
    rng = np.random.default_rng(1)
    g = newman_watts_strogatz(240, k=4, p=0.1, n_labels=5, seed=0)
    allp = enumerate_paths(g, np.arange(g.n_vertices, dtype=np.int32), 2)
    for qi in range(5):
        q = random_connected_query(g, int(rng.choice([4, 5, 6])), seed=qi)
        plan = plan_query(q, 2)
        cands = []
        for p in plan.paths:
            lab = q.labels[np.asarray(p)]
            cands.append(allp[np.all(g.labels[allp] == lab[None, :], axis=1)].astype(np.int32))
        ref = sort_matches(vf2_match(g, q, induced=induced))
        for impl in ("numpy", "device"):
            got = match_from_candidates(
                g, q, plan.paths, cands, induced=induced, join_impl=impl
            )
            assert sort_matches(got) == ref, (qi, impl)
        # refine() must also agree when fed the joined table directly
        table, cols = join_candidates(plan.paths, cands, n_values=g.n_vertices)
        for impl in ("numpy", "device"):
            got = refine(g, q, table, cols, induced=induced, impl=impl)
            assert sort_matches(got) == ref, (qi, impl, "refine")


def test_cartesian_no_shared_column_branch():
    """Disconnected query → a plan whose second path shares NO column
    with the table: the cartesian branch, in both implementations."""
    g = newman_watts_strogatz(120, k=4, p=0.1, n_labels=3, seed=2)
    # query: two disjoint labeled edges (labels copied from real edges)
    e = g.edge_array()
    e0, e1 = e[3], e[40]
    labs = np.asarray(
        [g.labels[e0[0]], g.labels[e0[1]], g.labels[e1[0]], g.labels[e1[1]]], np.int64
    )
    q = from_edge_list(4, np.asarray([[0, 1], [2, 3]]), labs)
    plan_paths = [(0, 1), (2, 3)]  # no shared query vertex: cartesian join
    edges_dir = np.concatenate([e, e[:, ::-1]], axis=0)  # both orientations
    cands = []
    for p in plan_paths:
        lab = q.labels[np.asarray(p)]
        m = (g.labels[edges_dir[:, 0]] == lab[0]) & (g.labels[edges_dir[:, 1]] == lab[1])
        cands.append(edges_dir[m].astype(np.int32))
    ref = sort_matches(vf2_match(g, q))
    assert ref, "oracle should find at least one disconnected-pattern match"
    for impl in ("numpy", "device"):
        got = match_from_candidates(g, q, plan_paths, cands, join_impl=impl)
        assert sort_matches(got) == ref, impl


def test_device_join_zero_pair_step_is_empty():
    """A join step whose keys match NOTHING must yield the empty result
    in both impls — the device driver's early exit must not hand back
    the stale pre-step table (review regression)."""
    g = newman_watts_strogatz(80, k=4, p=0.1, n_labels=2, seed=0)
    plan_paths = [(0, 1), (0, 2)]
    cands = [
        np.asarray([[1, 2], [3, 4]], np.int32),
        np.asarray([[5, 6]], np.int32),  # shares col 0, no key overlap
    ]
    t_np, c_np = join_candidates(plan_paths, cands, n_values=g.n_vertices)
    t_dev, c_dev = join_candidates(plan_paths, cands, n_values=g.n_vertices, impl="device")
    assert t_np.shape[0] == 0 and t_dev.shape[0] == 0
    assert t_dev.shape[1] == len(c_dev) == 3
    assert sorted(c_np) == sorted(c_dev)
    # full pipeline: empty match list, no assertion
    labs = np.asarray([0, 0, 0], np.int64)
    q = from_edge_list(3, np.asarray([[0, 1], [0, 2]]), labs)
    for impl in ("numpy", "device"):
        assert match_from_candidates(g, q, plan_paths, cands, join_impl=impl) == []


def test_join_candidates_dedup_contract():
    """Duplicate candidate rows (the general contract) must not produce
    duplicate matches in either implementation."""
    g = newman_watts_strogatz(150, k=4, p=0.1, n_labels=4, seed=3)
    q = random_connected_query(g, 5, seed=1)
    plan = plan_query(q, 2)
    allp = enumerate_paths(g, np.arange(g.n_vertices, dtype=np.int32), 2)
    cands = []
    for p in plan.paths:
        lab = q.labels[np.asarray(p)]
        c = allp[np.all(g.labels[allp] == lab[None, :], axis=1)].astype(np.int32)
        cands.append(np.concatenate([c, c[: max(1, c.shape[0] // 2)]]))  # force dups
    t_np, _ = join_candidates(plan.paths, cands, n_values=g.n_vertices)
    t_dev, _ = join_candidates(plan.paths, cands, n_values=g.n_vertices, impl="device")
    assert {tuple(r) for r in t_np} == {tuple(r) for r in t_dev}
    assert len({tuple(r) for r in t_np}) == t_np.shape[0], "numpy table has dups"
    assert len({tuple(r) for r in t_dev}) == t_dev.shape[0], "device table has dups"
    ref = sort_matches(vf2_match(g, q))
    for impl in ("numpy", "device"):
        got = match_from_candidates(g, q, plan.paths, cands, join_impl=impl)
        assert sort_matches(got) == ref, impl


# ---------------------------------------------------------------------------
# host edge-key overflow guard
# ---------------------------------------------------------------------------


def test_edge_key_overflow_guard():
    """``src·n + dst`` wraps past n ≈ 3.04e9; the structured fallback
    must keep distinct edges distinct and preserve sorted order."""
    n = 1 << 32  # pathological vertex-id space
    # the old packed-int64 key would ALIAS these two distinct edges:
    # 1·2³² + (x − 2³²) == 0·2³² + x  (mod 2⁶⁴)
    x = np.int64(5_000_000_000)
    src = np.asarray([0, 1], np.int64)
    dst = np.asarray([x, x - (1 << 32)], np.int64)
    keys = _edge_key_arrays(src, dst, n)
    assert keys[0] != keys[1], "distinct edges must have distinct keys"
    # order preserved: keys sorted iff (src, dst) pairs sorted
    src2 = np.asarray([0, 0, 1, 1, 2], np.int64)
    dst2 = np.asarray([1, n - 1, 0, 7, 3], np.int64)
    k2 = _edge_key_arrays(src2, dst2, n)
    assert (np.sort(k2) == k2).all()
    # membership via searchsorted against a probe built the same way
    want = _edge_key_arrays(np.asarray([1], np.int64), np.asarray([7], np.int64), n)
    pos = np.searchsorted(k2, want)
    assert k2[pos[0]] == want[0]
    miss = _edge_key_arrays(np.asarray([1], np.int64), np.asarray([8], np.int64), n)
    pos = np.minimum(np.searchsorted(k2, miss), k2.size - 1)
    assert k2[pos[0]] != miss[0]
    # small-n path still packs into int64 (fast path unchanged)
    k_small = _edge_key_arrays(src2, dst2, 1000)
    assert k_small.dtype == np.int64


# ---------------------------------------------------------------------------
# engine-level identity: kinds × join impls × delta states
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def engine_and_queries():
    g = newman_watts_strogatz(520, k=4, p=0.1, n_labels=8, seed=0)
    eng = GnnPeEngine(
        GnnPeConfig(
            encoder="monotone",
            n_partitions=3,
            n_multi=1,
            index_kind="grouped",
            quantize_index=True,
            train=TrainConfig(max_epochs=30),
        )
    ).build(g)
    queries = [
        random_connected_query(g, s, seed=100 + i) for i, s in enumerate([5, 6, 5])
    ]
    return eng, queries


def test_device_join_identity_sweep(engine_and_queries):
    eng, queries = engine_and_queries
    base = eng.match_many(queries, index_kind="path", join_impl="numpy")
    for q, b in zip(queries, base):
        assert set(b) == set(vf2_match(eng.graph, q))
    for kind in ("path", "grouped"):
        got = eng.match_many(queries, index_kind=kind, join_impl="device")
        for qi, (a, b) in enumerate(zip(got, base)):
            assert sort_matches(a) == sort_matches(b), (kind, qi)


def test_device_join_identity_under_delta(engine_and_queries):
    eng, queries = engine_and_queries
    rng = np.random.default_rng(7)
    for epoch in range(2):
        e = eng.graph.edge_array()
        eng.apply_updates(
            GraphUpdate(
                add_edges=rng.integers(0, eng.graph.n_vertices, (3, 2)),
                remove_edges=e[rng.choice(e.shape[0], 3, replace=False)],
            )
        )
        a = eng.match_many(queries, join_impl="numpy")
        b = eng.match_many(queries, join_impl="device")
        for qi, (q, x, y) in enumerate(zip(queries, a, b)):
            assert sort_matches(x) == sort_matches(y), (epoch, qi)
            assert set(x) == set(vf2_match(eng.graph, q)), (epoch, qi)


def test_stacked_device_join_no_host_expansion(engine_and_queries):
    """The acceptance property: with ``join_impl="device"`` the stacked
    probe's leaf member-expansion output feeds the join WITHOUT a
    host-side expansion round-trip (and the host path does expand)."""
    eng, queries = engine_and_queries
    probe = eng.stacked_probe()
    before = probe.host_expansions
    eng.match_many(queries, join_impl="device")
    assert probe.host_expansions == before, "device join expanded members on host"
    eng.match_many(queries, join_impl="numpy")
    assert probe.host_expansions > before, "host path should count its expansions"


def test_isomorphic_queries_share_one_join_group(engine_and_queries):
    """Relabeled-isomorphic queries join in canonical space as one
    vmapped group; per-query results must match the host join."""
    eng, _ = engine_and_queries
    g = eng.graph
    base = random_connected_query(g, 6, seed=42)
    rng = np.random.default_rng(9)
    batch = [base]
    for _ in range(3):
        perm = rng.permutation(base.n_vertices)
        e = base.edge_array()
        labs = np.empty(base.n_vertices, np.int64)
        labs[perm] = base.labels
        batch.append(
            from_edge_list(base.n_vertices, np.stack([perm[e[:, 0]], perm[e[:, 1]]], 1), labs)
        )
    a = eng.match_many(batch, join_impl="numpy")
    b = eng.match_many(batch, join_impl="device")
    for qi, (x, y) in enumerate(zip(a, b)):
        assert sort_matches(x) == sort_matches(y), qi
    # the isomorphic copies see permuted versions of the same match set
    canon = {tuple(sorted(m)) for m in a[0]}
    for matches in a[1:]:
        assert {tuple(sorted(m)) for m in matches} == canon


def test_scalar_impl_device_join(engine_and_queries):
    """``match`` (a batch of one) takes the ``join_impl`` override; both
    joins return VF2's match set."""
    eng, queries = engine_and_queries
    a = eng.match(queries[0], join_impl="numpy")
    b = eng.match(queries[0], join_impl="device")
    assert sort_matches(a) == sort_matches(b)
    assert set(a) == set(vf2_match(eng.graph, queries[0]))


def test_join_impl_validation(engine_and_queries):
    eng, queries = engine_and_queries
    with pytest.raises(ValueError, match="join_impl"):
        eng.match_many(queries, join_impl="bogus")
    with pytest.raises(ValueError, match="join impl"):
        join_candidates([(0, 1)], [np.zeros((0, 2), np.int32)], n_values=4, impl="bogus")


def test_device_join_shard_map_2dev():
    """The batched join's ("join",) mesh path: with >1 local device every
    vmapped step shard_maps over the query batch; results must equal the
    VF2 oracle (subprocess: XLA device count is fixed at import)."""
    import os
    import subprocess
    import sys
    import textwrap

    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import jax
        import numpy as np
        from repro.core import vf2_match
        from repro.core.matcher import match_from_candidates_many, sort_matches
        from repro.core.paths import enumerate_paths
        from repro.core.planner import plan_query
        from repro.graphs import from_edge_list, newman_watts_strogatz, random_connected_query

        assert len(jax.devices()) == 2
        g = newman_watts_strogatz(240, k=4, p=0.1, n_labels=5, seed=0)
        allp = enumerate_paths(g, np.arange(g.n_vertices, dtype=np.int32), 2)
        base = random_connected_query(g, 5, seed=1)
        rng = np.random.default_rng(2)
        queries = [base]
        for _ in range(2):  # 3 members: forces mesh padding to 4
            perm = rng.permutation(base.n_vertices)
            e = base.edge_array()
            labs = np.empty(base.n_vertices, np.int64)
            labs[perm] = base.labels
            queries.append(from_edge_list(
                base.n_vertices, np.stack([perm[e[:, 0]], perm[e[:, 1]]], 1), labs))
        plans, cands = [], []
        for q in queries:
            plan = plan_query(q, 2)
            plans.append(plan.paths)
            cl = []
            for p in plan.paths:
                lab = q.labels[np.asarray(p)]
                cl.append(allp[np.all(g.labels[allp] == lab[None, :], axis=1)].astype(np.int32))
            cands.append(cl)
        out = match_from_candidates_many(
            g, queries, plans, cands, join_impl="device", assume_unique=True
        )
        for q, m in zip(queries, out):
            assert sort_matches(m) == sort_matches(vf2_match(g, q))
        print("JOIN_SHARD_OK")
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=600,
        env={
            "PYTHONPATH": f"src{os.pathsep}.",
            "PATH": "/usr/bin:/bin:/usr/local/bin",
            **(
                {"JAX_PLATFORMS": os.environ["JAX_PLATFORMS"]}
                if "JAX_PLATFORMS" in os.environ
                else {}
            ),
        },
    )
    assert "JOIN_SHARD_OK" in proc.stdout, proc.stdout + proc.stderr[-3000:]


# ---------------------------------------------------------------------------
# satellite: per-partition auto group size
# ---------------------------------------------------------------------------


def test_choose_group_size_picks_candidate():
    from repro.core import build_index
    from repro.core.grouping import GROUP_SIZE_CANDIDATES, choose_group_size

    rng = np.random.default_rng(0)
    P, D = 4096, 4
    emb = rng.random((P, D)).astype(np.float32)
    # few label vectors → long homogeneous runs → big groups should win
    vocab = rng.random((2, D)).astype(np.float32)
    emb0 = vocab[rng.integers(0, 2, P)]
    ix = build_index(rng.integers(0, 50, (P, 3)).astype(np.int32), emb, emb0)
    g_big = choose_group_size(ix)
    assert g_big in GROUP_SIZE_CANDIDATES
    # every row a distinct label vector → every group mixed → small wins
    emb0_mixed = rng.random((P, D)).astype(np.float32)
    ix2 = build_index(rng.integers(0, 50, (P, 3)).astype(np.int32), emb, emb0_mixed)
    g_small = choose_group_size(ix2)
    assert g_small in GROUP_SIZE_CANDIDATES
    assert g_big >= g_small


def test_auto_group_size_engine_identical_matches():
    g = newman_watts_strogatz(420, k=4, p=0.1, n_labels=6, seed=1)
    qs = [random_connected_query(g, 5, seed=i) for i in range(2)]
    fixed = GnnPeEngine(
        GnnPeConfig(
            encoder="monotone", n_partitions=3, n_multi=1, index_kind="grouped",
            train=TrainConfig(max_epochs=25),
        )
    ).build(g)
    auto = GnnPeEngine(
        GnnPeConfig(
            encoder="monotone", n_partitions=3, n_multi=1, index_kind="grouped",
            group_size_mode="auto",
            train=TrainConfig(max_epochs=25),
        )
    ).build(g)
    sizes = auto.offline_stats["group_sizes"]
    assert sizes and all(s in (8, 16, 32) for s in sizes)
    a = fixed.match_many(qs)
    # auto sizes must not change match sets — including the stacked
    # group sidecar with heterogeneous gpb
    b = auto.match_many(qs)
    for qi, (q, x, y) in enumerate(zip(qs, a, b)):
        assert sort_matches(x) == sort_matches(y), qi
        assert set(y) == set(vf2_match(g, q)), qi


def test_stacked_probe_heterogeneous_group_sizes():
    """Partitions grouped at DIFFERENT sizes (what auto mode produces on
    real data) must stack — slot capacity follows the finest grouping —
    and probe identically to the per-partition reference traversal
    (``query_index_batch_multi``); a recompacted partition
    re-stacks in place iff its grouping fits the slot capacity."""
    from repro.core import build_index, query_index_batch_multi
    from repro.core.grouping import attach_groups
    from repro.core.stacked import restack_slot
    from repro.dist.probe import StackedProbe

    rng = np.random.default_rng(0)
    vocab = rng.random((4, 2)).astype(np.float32)
    indexes = []
    for i, gsz in enumerate([8, 32, 16]):
        P = 700 + 111 * i
        emb = rng.random((P, 4)).astype(np.float32)
        emb0 = vocab[rng.integers(0, 4, (P, 2))].reshape(P, 4)
        ix = build_index(
            rng.integers(0, 500, (P, 3)).astype(np.int32), emb, emb0, block_size=64
        )
        attach_groups(ix, gsz)
        indexes.append(ix)
    probe = StackedProbe(indexes)
    assert probe.stacked.groups.gpb == 8  # ceil(64 / min size 8)
    Q = 5
    q_emb = (rng.random((3, Q, 4)) * 0.8 + 0.1).astype(np.float32)
    q_emb0 = vocab[rng.integers(0, 4, (3, Q, 2))].reshape(3, Q, 4).astype(np.float32)
    items = [(ix, q_emb[i], q_emb0[i], None, None) for i, ix in enumerate(indexes)]
    for use_groups in (False, True):
        ref = query_index_batch_multi(items, use_pallas=False, use_groups=use_groups)
        got = probe.probe(q_emb, q_emb0, None, use_groups=use_groups, use_pallas=False)
        for i in range(3):
            for qi in range(Q):
                np.testing.assert_array_equal(ref[i][qi], got[i][qi])
    assert probe.update_slot(1, indexes[1])  # size-32 grouping fits gpb=8
    ix_fine = build_index(
        rng.integers(0, 500, (700, 3)).astype(np.int32),
        rng.random((700, 4)).astype(np.float32),
        vocab[rng.integers(0, 4, (700, 2))].reshape(700, 4),
        block_size=64,
    )
    attach_groups(ix_fine, 4)  # would need 16 slots/block > capacity 8
    assert not restack_slot(probe.stacked, int(probe.stacked.slot_of[0]), ix_fine)


def test_group_size_mode_validation():
    with pytest.raises(ValueError, match="group_size_mode"):
        GnnPeEngine(
            GnnPeConfig(encoder="monotone", group_size_mode="bogus")
        ).build(newman_watts_strogatz(60, k=4, p=0.1, n_labels=3, seed=0))


# ---------------------------------------------------------------------------
# satellite: cost-ranked MatchServer scheduling
# ---------------------------------------------------------------------------


def test_cost_ranked_schedule(engine_and_queries):
    from repro.serve.match_server import MatchServeConfig, MatchServer

    eng, _ = engine_and_queries
    g = eng.graph
    qs = [random_connected_query(g, s, seed=50 + i) for i, s in enumerate([8, 4, 6, 4])]
    fifo = MatchServer(eng, MatchServeConfig(max_batch=2, schedule="fifo"))
    cost = MatchServer(eng, MatchServeConfig(max_batch=2, schedule="cost"))
    rf = [fifo.submit(q) for q in qs]
    rc = [cost.submit(q) for q in qs]
    # first cost tick must hold the two cheapest queries (ties: rid order)
    order = sorted(range(len(qs)), key=lambda i: (eng.plan_cost(qs[i]), i))
    served = cost.step()
    assert served == 2
    first_tick = {rid for rid in rc if rid in cost.finished}
    assert first_tick == {rc[order[0]], rc[order[1]]}
    fifo.run_until_drained()
    cost.run_until_drained()
    for a, b in zip(rf, rc):
        assert sort_matches(fifo.finished[a]) == sort_matches(cost.finished[b])
    assert len(cost.tick_stats) == 2
    assert all(t["n_queries"] == 2 and t["wall_s"] > 0 for t in cost.tick_stats)
    assert cost.tick_stats[0]["min_cost"] is not None
    with pytest.raises(ValueError, match="schedule"):
        MatchServer(eng, MatchServeConfig(schedule="bogus"))


def test_cost_schedule_no_starvation(engine_and_queries):
    """A query that sorts LAST under the cost model must not be starved
    by a steady stream of better-ranked arrivals: the oldest queued
    request rides every tick."""
    from repro.serve.match_server import MatchServeConfig, MatchServer

    eng, _ = engine_and_queries
    g = eng.graph
    pool = [random_connected_query(g, 4 + i % 5, seed=300 + i) for i in range(8)]
    costs = [eng.plan_cost(q) for q in pool]
    worst = pool[int(np.argmax(costs))]  # sorts last every tick
    fillers = [q for q, c in zip(pool, costs) if c < max(costs)]
    assert len(fillers) >= 4
    srv = MatchServer(eng, MatchServeConfig(max_batch=2, schedule="cost"))
    rid_worst = srv.submit(worst)
    srv.submit(fillers[0])
    srv.submit(fillers[1])
    # keep refilling with better-ranked queries before each tick; without
    # the oldest-request guarantee the worst-ranked one never gets batched
    srv.submit(fillers[2])
    served = srv.step()
    assert served == 2
    assert rid_worst in srv.finished, "worst-ranked (oldest) query starved"
