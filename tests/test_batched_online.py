"""§Perf D batched online path: exact equivalence with the scalar index
traversal and with VF2, int8 grid-edge soundness, the engine's one
online path, and proof the Pallas kernel runs on the engine's REAL
query path (not just in kernel unit tests)."""
import dataclasses

import numpy as np
import pytest

import repro.core.index as index_mod
from repro.core import GnnPeConfig, GnnPeEngine, vf2_match
from repro.core.index import (
    build_index,
    hash_labels,
    quantize_data,
    quantize_query,
    query_index,
    query_index_batch,
)
from repro.graphs import erdos_renyi, newman_watts_strogatz, random_connected_query
from repro.serve.match_server import MatchServeConfig, MatchServer
from repro.serve.service import MatchService, ServiceConfig


# ------------------------------------------------ index-level equivalence ---


def _random_index_and_queries(seed, quantize):
    rng = np.random.default_rng(seed)
    P = int(rng.integers(200, 3000))
    D = int(rng.integers(2, 5)) * 2
    emb = rng.random((P, D)).astype(np.float32)
    lab_ids = rng.integers(0, 5, (P, D // 2)).astype(np.int32)
    lab_vocab = rng.random((5, 2)).astype(np.float32)
    emb0 = lab_vocab[lab_ids].reshape(P, D)
    emb_multi = rng.random((2, P, D)).astype(np.float32)
    paths = rng.integers(0, 100, (P, D // 2)).astype(np.int32)
    idx = build_index(
        paths, emb, emb0, emb_multi, block_size=int(rng.choice([32, 64, 128])),
        quantize=quantize, path_labels=lab_ids if quantize else None,
    )
    Q = int(rng.integers(1, 24))
    js = rng.integers(0, P, Q)
    q_emb = (emb[js] * rng.uniform(0.7, 1.0, (Q, 1))).astype(np.float32)
    q_emb0 = emb0[js]
    q_multi = (emb_multi[:, js] * rng.uniform(0.7, 1.0, (1, Q, 1))).astype(np.float32)
    qh = hash_labels(lab_ids[js]) if quantize else None
    return idx, q_emb, q_emb0, q_multi, qh


def test_query_index_batch_equals_single_property():
    """Property (seeded sweep): batched traversal returns exactly the rows
    and stats of Q independent single-query traversals."""
    for seed in range(12):
        quantize = bool(seed % 2)
        idx, q_emb, q_emb0, q_multi, qh = _random_index_and_queries(seed, quantize)
        for use_pallas in [False, True]:
            rows_b, stats_b = query_index_batch(
                idx, q_emb, q_emb0, q_multi, q_label_hash=qh,
                use_pallas=use_pallas, return_stats=True,
            )
            for qi in range(q_emb.shape[0]):
                rows_s, stats_s = query_index(
                    idx, q_emb[qi], q_emb0[qi], q_multi[:, qi],
                    q_label_hash=int(qh[qi]) if quantize else None, return_stats=True,
                )
                np.testing.assert_array_equal(np.sort(rows_s), np.sort(rows_b[qi]))
                assert stats_s == stats_b[qi]


# ------------------------------------------------- int8 grid boundary ------


def test_int8_quantization_grid_edge_no_false_dismissal():
    """q == e exactly ON a grid edge (e·scale integral) must never be
    dismissed: floor(q·s) == ceil(e·s) there, so the pre-filter keeps it."""
    grid = np.arange(0, 251, dtype=np.float64) / 250.0  # every int8 grid edge
    x = grid.astype(np.float32)
    assert np.all(quantize_query(x) <= quantize_data(x))
    # tiny fp wiggle around the edge must stay sound too (q <= e)
    for delta in [0.0, 1e-8, 1e-7]:
        q = np.clip(x - delta, 0, 1).astype(np.float32)
        assert np.all(quantize_query(q) <= quantize_data(x))


def test_quantized_index_keeps_exact_grid_edge_match():
    """End-to-end: an embedding sitting exactly on grid edges, queried
    with q == e, survives the quantized index (both impls)."""
    rng = np.random.default_rng(0)
    P, D = 500, 6
    emb = (rng.integers(0, 251, (P, D)) / 250.0).astype(np.float32)  # all on-grid
    lab_ids = rng.integers(0, 3, (P, 3)).astype(np.int32)
    lab_vocab = rng.random((3, 2)).astype(np.float32)
    emb0 = lab_vocab[lab_ids].reshape(P, 6)
    paths = rng.integers(0, 50, (P, 3)).astype(np.int32)
    idx = build_index(paths, emb, emb0, block_size=64, quantize=True, path_labels=lab_ids)
    for j in [0, 17, 499]:
        qh = int(hash_labels(lab_ids[j][None])[0])
        rows = query_index(idx, emb[j], emb0[j], q_label_hash=qh)
        # the row identical to the query (build_index re-sorts rows, so
        # locate it by value) must survive the quantized pre-filter
        same = np.nonzero(
            np.all(idx.emb == emb[j], axis=1) & np.all(idx.emb0 == emb0[j], axis=1)
        )[0]
        assert same.size, "planted row lost by the index build"
        missing = set(same.tolist()) - set(rows.tolist())
        assert not missing, f"grid-edge q==e dismissed (j={j}): {missing}"
        # batched agrees
        rows_b = query_index_batch(
            idx, emb[j][None], emb0[j][None], q_label_hash=np.asarray([qh])
        )[0]
        np.testing.assert_array_equal(np.sort(rows), np.sort(rows_b))


# ------------------------------------------------- engine equivalence ------


def test_match_many_equals_scalar_property():
    """Property (seeded sweep over random graphs/queries): match_many ==
    the same query matched alone (byte-identical) == VF2 oracle."""
    for seed in range(4):
        rng = np.random.default_rng(seed)
        g = erdos_renyi(int(rng.integers(60, 140)), avg_degree=3.5, n_labels=int(rng.integers(3, 6)), seed=seed)
        cfg = GnnPeConfig(
            n_partitions=int(rng.integers(1, 4)), encoder="monotone",
            n_multi=int(seed % 3), block_size=32,
            quantize_index=bool(seed % 2), plan_weight="dr" if seed == 3 else "deg",
        )
        eng = GnnPeEngine(cfg).build(g)
        queries = []
        for s in range(5):
            try:
                queries.append(random_connected_query(g, 4 + s % 3, seed=100 * seed + s))
            except RuntimeError:
                continue
        if not queries:
            continue
        batched = eng.match_many(queries)
        for qi, q in enumerate(queries):
            alone = eng.match(q)
            assert batched[qi] == alone, f"seed {seed} query {qi}"
            assert set(alone) == set(vf2_match(g, q)), f"seed {seed} query {qi}"


@pytest.mark.parametrize(
    "make,field,value",
    [
        (lambda eng, kw: GnnPeEngine(GnnPeConfig(**kw)), "probe_impl", "loop"),
        (lambda eng, kw: GnnPeEngine(GnnPeConfig(**kw)), "online_impl", "scalar"),
        (lambda eng, kw: MatchService(eng, ServiceConfig(**kw)), "probe_impl", "loop"),
    ],
    ids=["engine-probe", "engine-online", "service-probe"],
)
def test_removed_online_paths_rejected(make, field, value):
    """The stacked, batched pipeline is the only online path: the config
    fields kept for existing configuration files accept nothing else."""
    with pytest.raises(ValueError, match=field):
        make(GnnPeEngine(GnnPeConfig()), {field: value})


def test_engine_real_path_invokes_pallas_kernel():
    """Integration (acceptance): with use_pallas_scan=True the engine's
    real match path runs the Pallas dominance kernel, and the NumPy
    reference (use_pallas_scan=False) returns identical matches."""
    g = newman_watts_strogatz(100, k=4, p=0.15, n_labels=4, seed=3)
    eng = GnnPeEngine(
        GnnPeConfig(n_partitions=2, encoder="monotone", use_pallas_scan=True)
    ).build(g)
    q = random_connected_query(g, 5, seed=9)
    before = index_mod.PALLAS_SCAN_CALLS
    matches = eng.match(q)
    assert index_mod.PALLAS_SCAN_CALLS > before, "Pallas kernel not invoked on engine path"
    eng.cfg = dataclasses.replace(eng.cfg, use_pallas_scan=False)
    assert eng.match(q) == matches
    assert set(matches) == set(vf2_match(g, q))


# ---------------------------------------------------------- MatchServer ----


def test_match_server_drains_and_is_exact():
    g = newman_watts_strogatz(100, k=4, p=0.15, n_labels=4, seed=5)
    eng = GnnPeEngine(GnnPeConfig(n_partitions=2, encoder="monotone")).build(g)
    srv = MatchServer(eng, MatchServeConfig(max_batch=4))
    queries, rids = [], []
    for s in range(10):  # > 2 ticks worth
        q = random_connected_query(g, 5, seed=40 + s)
        queries.append(q)
        rids.append(srv.submit(q))
    served = srv.step()
    assert served == 4  # one tick = one fused batch
    out = srv.run_until_drained()
    assert set(out) == set(rids)
    for rid, q in zip(rids, queries):
        assert set(out[rid]) == set(vf2_match(g, q))
        assert rid in srv.latency_s
