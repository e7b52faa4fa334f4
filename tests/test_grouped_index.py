"""GNN-PGE grouped index: grouping-pass structure, two-level probe
equivalence with the per-path probe, group-MBR soundness on adversarial
embeddings (grid edges / duplicate vectors), and engine-level match-set
equivalence across quantized and plan_weight="dr" configs."""
import numpy as np

import repro.core.index as index_mod
from repro.core import GnnPeConfig, GnnPeEngine, vf2_match
from repro.core.grouping import attach_groups, group_paths
from repro.core.index import (
    build_index,
    hash_labels,
    query_index,
    query_index_batch,
    reset_pair_counters,
)
from repro.graphs import erdos_renyi, random_connected_query


def _random_index(seed, quantize, n_labels=5):
    rng = np.random.default_rng(seed)
    P = int(rng.integers(200, 3000))
    D = int(rng.integers(2, 5)) * 2
    emb = rng.random((P, D)).astype(np.float32)
    lab_ids = rng.integers(0, n_labels, (P, D // 2)).astype(np.int32)
    lab_vocab = rng.random((n_labels, 2)).astype(np.float32)
    emb0 = lab_vocab[lab_ids].reshape(P, D)
    emb_multi = rng.random((2, P, D)).astype(np.float32)
    paths = rng.integers(0, 100, (P, D // 2)).astype(np.int32)
    idx = build_index(
        paths, emb, emb0, emb_multi, block_size=int(rng.choice([32, 64, 128])),
        quantize=quantize, path_labels=lab_ids if quantize else None,
    )
    return idx, rng, emb, emb0, emb_multi, lab_ids


# ------------------------------------------------------ grouping pass ------


def test_grouping_pass_structure():
    """Groups tile the sorted order: contiguous, ≤ group_size, block-aligned,
    with MBRs that exactly bound their members."""
    for seed in range(6):
        idx, rng, *_ = _random_index(seed, quantize=False)
        gsz = int(rng.choice([4, 8, 16]))
        g = group_paths(idx, gsz)
        P = idx.n_paths
        assert g.group_start[0] == 0 and g.group_start[-1] == P
        counts = np.diff(g.group_start)
        assert np.all(counts >= 1) and np.all(counts <= gsz)
        # never crosses a leaf-block edge → block b owns groups
        # [block_group_start[b], block_group_start[b+1])
        bs = idx.block_size
        s, e = g.group_start[:-1], g.group_start[1:]
        assert np.all(s // bs == (e - 1) // bs)
        blocks = s // bs
        np.testing.assert_array_equal(
            g.block_group_start, np.searchsorted(blocks, np.arange(blocks.max() + 2))
        )
        # exact bounds (checking every group is cheap at this scale)
        n_gnn = idx.emb_multi.shape[0]
        cat = np.concatenate([idx.emb] + [idx.emb_multi[i] for i in range(n_gnn)], axis=1)
        for k in range(g.n_groups):
            a, b = g.group_start[k], g.group_start[k + 1]
            np.testing.assert_array_equal(g.mbr_hi[k], cat[a:b].max(0))
            np.testing.assert_array_equal(g.mbr0[k, :, 0], idx.emb0[a:b].min(0))
            np.testing.assert_array_equal(g.mbr0[k, :, 1], idx.emb0[a:b].max(0))


def test_group_sidecar_nbytes_accounted():
    idx, *_ = _random_index(0, quantize=True)
    base = idx.nbytes()
    attach_groups(idx, 8)
    assert idx.groups is not None and idx.groups.nbytes() > 0
    assert idx.nbytes() == base + idx.groups.nbytes()
    st = idx.groups.stats()
    assert st["n_groups"] == idx.groups.n_groups and st["group_bytes"] > 0


# ---------------------------------------------- probe equivalence ----------


def test_grouped_probe_equals_per_path_property():
    """Property (seeded sweep): the two-level grouped probe returns exactly
    the per-path probe's rows, on both backends, while issuing fewer (or
    equal) leaf-level pairs."""
    for seed in range(10):
        quantize = bool(seed % 2)
        idx, rng, emb, emb0, emb_multi, lab_ids = _random_index(seed, quantize)
        attach_groups(idx, int(rng.choice([4, 8, 16])))
        P = idx.n_paths
        Q = int(rng.integers(1, 24))
        js = rng.integers(0, P, Q)
        q_emb = (emb[js] * rng.uniform(0.7, 1.0, (Q, 1))).astype(np.float32)
        q_emb0 = emb0[js]
        q_multi = (emb_multi[:, js] * rng.uniform(0.7, 1.0, (1, Q, 1))).astype(np.float32)
        qh = hash_labels(lab_ids[js]) if quantize else None
        for use_pallas in [False, True]:
            reset_pair_counters()
            rows_p = query_index_batch(
                idx, q_emb, q_emb0, q_multi, q_label_hash=qh, use_pallas=use_pallas
            )
            lp_path = index_mod.PAIR_COUNTERS["leaf_pairs"]
            reset_pair_counters()
            rows_g, stats_g = query_index_batch(
                idx, q_emb, q_emb0, q_multi, q_label_hash=qh,
                use_pallas=use_pallas, use_groups=True, return_stats=True,
            )
            lp_grouped = index_mod.PAIR_COUNTERS["leaf_pairs"]
            for qi in range(Q):
                np.testing.assert_array_equal(rows_p[qi], rows_g[qi])
                assert stats_g[qi]["surviving_groups"] <= stats_g[qi]["scanned_groups"]
            assert lp_grouped <= lp_path


def test_grouped_probe_requires_sidecar():
    idx, rng, emb, emb0, emb_multi, _ = _random_index(1, quantize=False)
    try:
        query_index_batch(idx, emb[:2], emb0[:2], emb_multi[:, :2], use_groups=True)
    except ValueError as e:
        assert "attach_groups" in str(e)
    else:
        raise AssertionError("grouped probe without sidecar should raise")


# ------------------------------------------- adversarial MBR soundness -----


def test_group_mbr_soundness_duplicate_vectors():
    """All-identical embeddings collapse every group MBR to a point; a query
    equal to the common vector must retrieve every row (q == e is the
    dominance boundary), a query epsilon above must retrieve none."""
    P, D = 1000, 6
    emb = np.full((P, D), 0.5, np.float32)
    emb0 = np.full((P, D), 0.25, np.float32)
    paths = np.zeros((P, 3), np.int32)
    idx = build_index(paths, emb, emb0, block_size=64)
    attach_groups(idx, 8)
    q = np.full((1, D), 0.5, np.float32)
    q0 = np.full((1, D), 0.25, np.float32)
    rows = query_index_batch(idx, q, q0, use_groups=True)[0]
    assert rows.size == P, "duplicate-vector group MBRs dismissed true matches"
    rows_hi = query_index_batch(idx, q + 0.01, q0, use_groups=True)[0]
    assert rows_hi.size == 0
    rows_lab = query_index_batch(idx, q, q0 + 0.01, use_groups=True)[0]
    assert rows_lab.size == 0


def test_group_mbr_soundness_grid_edges():
    """Embeddings exactly on int8 grid edges, queried with q == e through the
    quantized grouped index: the planted row must always survive (no false
    dismissal from group bounds composing with the int8 pre-filter)."""
    rng = np.random.default_rng(0)
    P, D = 500, 6
    emb = (rng.integers(0, 251, (P, D)) / 250.0).astype(np.float32)  # all on-grid
    lab_ids = rng.integers(0, 3, (P, 3)).astype(np.int32)
    lab_vocab = rng.random((3, 2)).astype(np.float32)
    emb0 = lab_vocab[lab_ids].reshape(P, 6)
    paths = rng.integers(0, 50, (P, 3)).astype(np.int32)
    idx = build_index(paths, emb, emb0, block_size=64, quantize=True, path_labels=lab_ids)
    attach_groups(idx, 4)
    for j in [0, 17, 499]:
        qh = np.asarray([int(hash_labels(lab_ids[j][None])[0])])
        expected = query_index(idx, emb[j], emb0[j], q_label_hash=int(qh[0]))
        same = np.nonzero(
            np.all(idx.emb == emb[j], axis=1) & np.all(idx.emb0 == emb0[j], axis=1)
        )[0]
        assert same.size, "planted row lost by the index build"
        for use_pallas in [False, True]:
            rows = query_index_batch(
                idx, emb[j][None], emb0[j][None], q_label_hash=qh,
                use_pallas=use_pallas, use_groups=True,
            )[0]
            missing = set(same.tolist()) - set(rows.tolist())
            assert not missing, f"grid-edge q==e dismissed by grouped probe (j={j})"
            np.testing.assert_array_equal(np.sort(expected), np.sort(rows))


# ------------------------------------------------- engine equivalence ------


def test_engine_grouped_equals_path_property():
    """Property (seeded sweep): a grouped engine's match_many equals the
    per-path probe byte-for-byte (deg plans) / set-for-set (dr plans,
    where the grouped cost model may order plans differently), and both
    equal the VF2 oracle."""
    for seed in range(4):
        rng = np.random.default_rng(seed)
        g = erdos_renyi(
            int(rng.integers(60, 140)), avg_degree=3.5,
            n_labels=int(rng.integers(3, 6)), seed=seed,
        )
        dr = seed == 3
        cfg = GnnPeConfig(
            n_partitions=int(rng.integers(1, 4)), encoder="monotone",
            n_multi=int(seed % 3), block_size=32,
            index_kind="grouped", group_size=int(rng.choice([4, 8])),
            quantize_index=bool(seed % 2), plan_weight="dr" if dr else "deg",
        )
        eng = GnnPeEngine(cfg).build(g)
        queries = []
        for s in range(4):
            try:
                queries.append(random_connected_query(g, 4 + s % 3, seed=100 * seed + s))
            except RuntimeError:
                continue
        if not queries:
            continue
        reset_pair_counters()
        grouped = eng.match_many(queries)  # cfg default: grouped probe
        lp_grouped = index_mod.PAIR_COUNTERS["leaf_pairs"]
        reset_pair_counters()
        per_path = eng.match_many(queries, index_kind="path")
        lp_path = index_mod.PAIR_COUNTERS["leaf_pairs"]
        assert lp_grouped <= lp_path
        for qi, q in enumerate(queries):
            if dr:
                assert sorted(grouped[qi]) == sorted(per_path[qi]), f"seed {seed} q {qi}"
            else:
                assert grouped[qi] == per_path[qi], f"seed {seed} q {qi}"
            assert set(grouped[qi]) == set(vf2_match(g, q)), f"seed {seed} q {qi}"
        assert eng.offline_stats["n_groups"] > 0
        assert eng.offline_stats["group_bytes"] > 0


def test_engine_grouped_pallas_kernel_on_real_path():
    """With use_pallas_scan=True a grouped engine's real match path runs
    BOTH probe levels: the group-MBR level in the stacked probe's device
    descent, the member level in the fused Pallas kernel."""
    g = erdos_renyi(100, avg_degree=3.5, n_labels=4, seed=7)
    eng = GnnPeEngine(
        GnnPeConfig(
            n_partitions=2, encoder="monotone", index_kind="grouped",
            group_size=4, use_pallas_scan=True,
        )
    ).build(g)
    q = random_connected_query(g, 5, seed=3)
    before = index_mod.PALLAS_SCAN_CALLS
    groups_before = index_mod.PAIR_COUNTERS["group_pairs"]
    matches = eng.match_many([q])[0]
    assert index_mod.PAIR_COUNTERS["group_pairs"] > groups_before, "no group-level scan"
    assert index_mod.PALLAS_SCAN_CALLS >= before + 1, "no member scan in the kernel"
    assert set(matches) == set(vf2_match(g, q))
