"""Stacked-tensor partition index + sharded probe (core/stacked.py,
dist/probe.py): probe equivalence with the per-partition loop traversal
across index kinds / quantization / ragged partition shapes, shard-
balanced layout, padding accounting, the 4-virtual-device shard_map
path, and the plan-cache + pre-hashed-join satellites."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import repro.core.index as index_mod
from repro.core import (
    GnnPeConfig,
    GnnPeEngine,
    build_index,
    build_stacked,
    canonical_form,
    plan_shards,
    query_index_batch_multi,
    reset_pair_counters,
    vf2_match,
)
from repro.core.grouping import attach_groups
from repro.core.index import hash_labels
from repro.core.matcher import _lex_keys, _unique_rows
from repro.core.stacked import stacked_masks_ref
from repro.dist.probe import StackedProbe
from repro.graphs import erdos_renyi, random_connected_query


def _ragged_indexes(seed: int, quantize: bool, n_gnn: int = 2, n_labels: int = 5):
    """Partition set with adversarial raggedness: a multi-level partition,
    a single-leaf-block one, a ONE-path one, a zero-path one, and one
    whose label vocabulary is disjoint from every query (empties out
    after the label filter).  All share the build geometry, as one
    engine build would."""
    rng = np.random.default_rng(seed)
    vocab = rng.random((n_labels, 2)).astype(np.float32)
    alien_vocab = (vocab + 7.0).astype(np.float32)  # disjoint label embeddings
    L = 3  # path length 2 → 3 vertices, D = 6
    D = 2 * L
    bs = 32

    def make(P, voc):
        emb = rng.random((P, D)).astype(np.float32)
        lab = rng.integers(0, n_labels, (P, L)).astype(np.int32)
        emb0 = voc[lab].reshape(P, D)
        emb_multi = rng.random((n_gnn, P, D)).astype(np.float32)
        paths = rng.integers(0, 100, (P, L)).astype(np.int32)
        return build_index(
            paths, emb, emb0, emb_multi, block_size=bs,
            quantize=quantize, path_labels=lab if quantize else None,
        ), lab

    sizes = [900, 20, 1, 0, 300]  # last uses the alien vocab
    out = []
    for i, P in enumerate(sizes):
        voc = alien_vocab if i == len(sizes) - 1 else vocab
        out.append(make(P, voc))
    indexes = [ix for ix, _ in out]
    return indexes, vocab, rng


def _queries(indexes, vocab, rng, Q, quantize, n_gnn):
    """Per-partition query embeddings + shared label-path hashes, shaped
    like the engine feeds the probe: (m, Q, D) / (n_gnn, m, Q, D)."""
    L = 3
    D = 2 * L
    lab = rng.integers(0, vocab.shape[0], (Q, L)).astype(np.int32)
    q_emb0 = np.broadcast_to(
        vocab[lab].reshape(Q, D), (len(indexes), Q, D)
    ).astype(np.float32)
    q_emb = rng.random((len(indexes), Q, D)).astype(np.float32) * 0.8
    q_multi = rng.random((n_gnn, len(indexes), Q, D)).astype(np.float32) * 0.8
    qh = hash_labels(lab) if quantize else None
    return q_emb, q_emb0, q_multi, qh


@pytest.mark.parametrize("kind", ["path", "grouped"])
@pytest.mark.parametrize("quantize", [False, True])
def test_stacked_probe_equals_loop_sweep(kind, quantize):
    """The stacked probe returns the loop traversal's rows byte-for-byte —
    both backends, both device stages — on ragged partitions including
    1-path, 0-path and label-disjoint ones, with matching stats."""
    for seed in range(3):
        n_gnn = seed % 3
        indexes, vocab, rng = _ragged_indexes(seed, quantize, n_gnn=n_gnn)
        use_groups = kind == "grouped"
        if use_groups:
            gsz = int(rng.choice([4, 8, 16]))  # one size per build, like the engine
            for ix in indexes:
                attach_groups(ix, gsz)
        Q = int(rng.integers(1, 12))
        q_emb, q_emb0, q_multi, qh = _queries(indexes, vocab, rng, Q, quantize, n_gnn)
        items = [
            (ix, q_emb[i], q_emb0[i], q_multi[:, i] if n_gnn else None, qh)
            for i, ix in enumerate(indexes)
        ]
        probe = StackedProbe(indexes)  # local devices (1 on tier-1 CI)
        for use_pallas in [False, True]:
            reset_pair_counters()
            ref, ref_stats = query_index_batch_multi(
                items, use_pallas=use_pallas, use_groups=use_groups, return_stats=True
            )
            ref_counters = dict(index_mod.PAIR_COUNTERS)
            for device_stage in ["numpy", "jit"]:
                reset_pair_counters()
                got, got_stats = probe.probe(
                    q_emb, q_emb0, q_multi if n_gnn else None, q_label_hash=qh,
                    use_groups=use_groups, use_pallas=use_pallas,
                    return_stats=True, device_stage=device_stage,
                )
                assert dict(index_mod.PAIR_COUNTERS) == ref_counters
                for i in range(len(indexes)):
                    for qi in range(Q):
                        np.testing.assert_array_equal(ref[i][qi], got[i][qi])
                        assert got[i][qi].dtype == np.int64
                        if indexes[i].n_paths:
                            assert ref_stats[i][qi] == got_stats[i][qi]


def test_stacked_levels_and_masks_reference():
    """The dense mask reference reproduces the loop descent's per-block
    survival on every real block, and padding slots never survive."""
    indexes, vocab, rng = _ragged_indexes(7, quantize=False, n_gnn=0)
    live = [ix for ix in indexes if ix.n_paths]
    st = build_stacked(indexes, n_shards=1)
    Q = 5
    q_emb, q_emb0, _, _ = _queries(indexes, vocab, rng, Q, False, 0)
    q_cat = np.zeros((st.n_slots, Q, q_emb.shape[2]), np.float32)
    q0 = np.zeros((st.n_slots, Q, q_emb0.shape[2]), np.float32)
    q_cat[st.slot_of] = q_emb
    q0[st.slot_of] = q_emb0
    alive, _ = stacked_masks_ref(st, q_cat, q0)
    for i, ix in enumerate(indexes):
        s = int(st.slot_of[i])
        nb = ix.levels[0]["mbr"].shape[0] if ix.levels else 0
        assert not alive[s, :, nb:].any(), "padded blocks must never survive"
        if ix.n_paths == 0:
            continue
        cand, loop_alive = index_mod._descend_batch(
            ix, q_emb[i], q_emb0[i], np.zeros((0, Q, q_emb.shape[2]), np.float32), 1e-6
        )
        dense = np.zeros((Q, nb), bool)
        dense[:, cand] = loop_alive
        np.testing.assert_array_equal(alive[s, :, :nb], dense)
    assert live, "fixture must keep non-empty partitions"


def test_plan_shards_balanced_and_padding_reported():
    sizes = np.asarray([100, 1, 90, 10, 80, 20, 70, 30])
    shards = plan_shards(sizes, 4)
    assert sorted(p for s in shards for p in s) == list(range(8))
    loads = [int(sizes[list(s)].sum()) for s in shards]
    assert max(loads) - min(loads) <= 20  # greedy keeps shards near-equal
    indexes, _, _ = _ragged_indexes(3, quantize=True)
    st = build_stacked(indexes, n_shards=4)
    assert st.n_slots % 4 == 0
    stats = st.padding_stats()
    assert stats["stacked_bytes"] >= stats["stacked_real_bytes"] > 0
    assert 0.0 <= stats["stacked_padding_frac"] < 1.0
    assert st.nbytes() == stats["stacked_bytes"]


def test_engine_stacked_equals_loop_and_oracle():
    """Engine-level byte identity between probe impls, against VF2, with
    stacked padding overhead reported in offline_stats."""
    g = erdos_renyi(140, avg_degree=3.5, n_labels=4, seed=5)
    for seed, kind in [(0, "path"), (1, "grouped")]:
        cfg = GnnPeConfig(
            n_partitions=3, encoder="monotone", n_multi=seed, block_size=32,
            index_kind=kind, group_size=4, quantize_index=bool(seed),
            probe_impl="stacked",
        )
        eng = GnnPeEngine(cfg).build(g)
        assert eng.offline_stats["stacked_bytes"] > 0
        assert "stacked_padding_frac" in eng.offline_stats
        queries = [random_connected_query(g, 4 + s % 3, seed=50 + s) for s in range(4)]
        stacked = eng.match_many(queries)  # cfg default: stacked probe
        loop = eng.match_many(queries, probe_impl="loop")
        for qi, q in enumerate(queries):
            assert stacked[qi] == loop[qi], f"{kind} q{qi}"
            assert set(stacked[qi]) == set(vf2_match(g, q))


def test_stacked_probe_shard_map_4dev():
    """shard_map over 4 virtual host devices returns the single-device
    rows (subprocess: XLA device count is fixed at import)."""
    script = textwrap.dedent(
        """
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        import jax
        import numpy as np
        from tests.test_stacked_probe import _ragged_indexes, _queries
        from repro.core import query_index_batch_multi
        from repro.core.grouping import attach_groups
        from repro.dist.probe import StackedProbe

        assert len(jax.devices()) == 4
        indexes, vocab, rng = _ragged_indexes(11, quantize=True)
        for ix in indexes:
            attach_groups(ix, 8)
        q_emb, q_emb0, q_multi, qh = _queries(indexes, vocab, rng, 6, True, 2)
        probe = StackedProbe(indexes)  # all 4 devices -> ("part",) mesh
        assert probe.mesh is not None and probe.stacked.n_shards == 4
        items = [
            (ix, q_emb[i], q_emb0[i], q_multi[:, i], qh)
            for i, ix in enumerate(indexes)
        ]
        for use_groups in [False, True]:
            ref = query_index_batch_multi(items, use_pallas=False, use_groups=use_groups)
            got = probe.probe(
                q_emb, q_emb0, q_multi, q_label_hash=qh,
                use_groups=use_groups, use_pallas=False,
            )
            for i in range(len(indexes)):
                for qi in range(6):
                    np.testing.assert_array_equal(ref[i][qi], got[i][qi])
            # device-resident assembly over the same mesh: the candidate
            # path vertices of every partition, in slot order
            per_b, _ = probe.probe_device(
                q_emb, q_emb0, q_multi, q_label_hash=qh,
                use_groups=use_groups, use_pallas=False,
            )
            for qi, (verts, n) in enumerate(per_b):
                want = [tuple(p) for i, ix in enumerate(indexes) for p in ix.paths[ref[i][qi]]]
                got_v = [tuple(p) for p in np.asarray(verts)[:n]]
                assert sorted(got_v) == sorted(want), (use_groups, qi)
        print("STACKED_SHARD_OK")
        """
    )
    proc = subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=600,
        env={"PYTHONPATH": f"src{os.pathsep}.", "PATH": "/usr/bin:/bin:/usr/local/bin",
             **({"JAX_PLATFORMS": os.environ["JAX_PLATFORMS"]} if "JAX_PLATFORMS" in os.environ else {})},
    )
    assert "STACKED_SHARD_OK" in proc.stdout, proc.stdout + proc.stderr[-3000:]


def test_stacked_grouped_probe_all_empty_partitions():
    """Every partition empty (no length-L paths): the stacked probe must
    return empty rows like the loop probe, even under use_groups where
    no group sidecar could have been stacked — not raise."""
    D = 6
    empty = build_index(
        np.zeros((0, 3), np.int32), np.zeros((0, D), np.float32),
        np.zeros((0, D), np.float32), block_size=32,
    )
    probe = StackedProbe([empty, empty])
    q = np.zeros((2, 3, D), np.float32)
    for use_groups in [False, True]:
        got, stats = probe.probe(q, q, use_groups=use_groups, return_stats=True)
        assert all(r.size == 0 for per in got for r in per)
        assert all(s["scanned_blocks"] == 0 for per in stats for s in per)
    # a live partition without the sidecar must still raise under use_groups
    one, _, _ = _ragged_indexes(0, quantize=False, n_gnn=0)
    live_probe = StackedProbe(one)
    with pytest.raises(ValueError, match="attach_groups"):
        live_probe.probe(
            np.zeros((len(one), 1, D), np.float32),
            np.zeros((len(one), 1, D), np.float32),
            use_groups=True,
        )


# ------------------------------------------------------ satellites ---------


def test_plan_cache_reuses_isomorphic_queries():
    """Relabeled-isomorphic queries hit one cached canonical plan; match
    sets stay exact."""
    g = erdos_renyi(120, avg_degree=3.5, n_labels=3, seed=9)
    eng = GnnPeEngine(GnnPeConfig(n_partitions=2, encoder="monotone", n_multi=0)).build(g)
    q = random_connected_query(g, 5, seed=4)
    rng = np.random.default_rng(0)
    # same query under a random vertex renumbering
    perm = rng.permutation(q.n_vertices)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(q.n_vertices)
    from repro.graphs import from_edge_list

    q2 = from_edge_list(
        q.n_vertices, [(int(inv[u]), int(inv[v])) for u, v in q.edge_array()],
        labels=q.labels[perm],
    )
    _, key1 = canonical_form(q)
    _, key2 = canonical_form(q2)
    matches = eng.match_many([q, q2, q])
    if key1 == key2:  # refinement aligned the relabeling → one planner run
        assert len(eng._plan_cache) == 1
    assert len(eng._plan_cache) >= 1
    assert set(matches[0]) == set(vf2_match(g, q))
    assert set(matches[1]) == set(vf2_match(g, q2))
    assert matches[0] == matches[2]  # identical query, identical plan+result
    # mapped-back sets agree up to the renumbering (q2 vertex j ≡ q vertex perm[j])
    assert {tuple(m[int(perm[j])] for j in range(q.n_vertices)) for m in matches[0]} == {
        tuple(m) for m in matches[1]
    }


def test_lex_keys_and_unique_rows_match_np_unique():
    rng = np.random.default_rng(0)
    for n_values, cols in [(50, 3), (2**20, 4)]:  # uint64 pack and void fallback
        a = rng.integers(0, n_values, (200, cols)).astype(np.int32)
        a = np.concatenate([a, a[:40]])  # force duplicates
        np.testing.assert_array_equal(_unique_rows(a, n_values), np.unique(a, axis=0))
        keys = _lex_keys(a, n_values)
        order_keys = np.argsort(keys, kind="stable")
        order_lex = np.lexsort(a.T[::-1])
        np.testing.assert_array_equal(a[order_keys], a[order_lex])
