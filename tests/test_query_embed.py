"""Query-side star embedding (core/engine.py): the fused per-tick program
``_embed_query_stars`` over power-of-two star rows equals the per-model
``_query_node_embeddings`` row for row (bit for bit with the monotone
encoder), counts its real and padded rows, and compiles one program per
row bucket."""
import functools

import numpy as np
import pytest

from repro.core import GnnPeConfig, GnnPeEngine
from repro.core.engine import _M_EMBED_ROWS, _embed_query_stars
from repro.core.training import TrainConfig
from repro.graphs import Graph, erdos_renyi, from_edge_list, random_connected_query


@functools.lru_cache(maxsize=None)
def _engine(encoder: str, n_multi: int) -> GnnPeEngine:
    g = erdos_renyi(160, avg_degree=3.5, n_labels=5, seed=3)
    cfg = GnnPeConfig(
        n_partitions=3, encoder=encoder, n_multi=n_multi, seed=0,
        train=TrainConfig(max_epochs=2, check_every=1),
    )
    return GnnPeEngine(cfg).build(g)


def _hub_query(theta: int) -> Graph:
    """A star whose centre has degree theta + 1 (an overflow star) with a
    tail, so every other star stays under the threshold."""
    n = theta + 3
    edges = [(0, i) for i in range(1, theta + 2)] + [(1, theta + 2)]
    return from_edge_list(n, edges, np.arange(n) % 5)


def _queries(case: str, eng: GnnPeEngine) -> list:
    g = eng.graph
    if case == "one4":
        return [random_connected_query(g, 4, seed=11)]
    if case == "three12":
        return [random_connected_query(g, k, seed=20 + k) for k in (3, 4, 5)]
    return [_hub_query(eng.cfg.theta), random_connected_query(g, 4, seed=31)]


def _rows():
    return _M_EMBED_ROWS.get(kind="real"), _M_EMBED_ROWS.get(kind="pad")


@pytest.mark.parametrize("n_multi", [0, 2])
@pytest.mark.parametrize("encoder", ["monotone", "gat"])
@pytest.mark.parametrize(
    "case, real, pad",
    [("one4", 4, 0), ("three12", 12, 4), ("overflow", 17, 15)],
)
def test_fused_query_embeddings_equal_per_model(encoder, n_multi, case, real, pad):
    eng = _engine(encoder, n_multi)
    queries = _queries(case, eng)
    assert sum(q.n_vertices for q in queries) == real
    real0, pad0 = _rows()
    cat, spans = eng._query_node_embeddings_many(queries)
    real1, pad1 = _rows()
    assert (real1 - real0, pad1 - pad0) == (real, pad)
    assert len(cat) == len(eng.models)
    if case == "overflow":
        assert queries[0].degrees[0] > eng.cfg.theta
    for qi, q in enumerate(queries):
        rows = slice(spans[qi], spans[qi + 1])
        for mi, model in enumerate(eng.models):
            o, o0, om = cat[mi]
            want = eng._query_node_embeddings(q, model)
            got = (o[rows], o0[rows], om[:, rows])
            for w, x in zip(want, got):
                assert x.dtype == np.float32 and x.shape == w.shape
                if encoder == "monotone":
                    np.testing.assert_array_equal(x, w)
                else:
                    # XLA's CPU code for the GAT rounds by the batch's row
                    # count: up to 2 ulp, as the eager per-call vmap did
                    np.testing.assert_array_max_ulp(x, w, maxulp=2)
            if case == "overflow" and qi == 0:
                assert not o[rows][0].any() and not om[:, rows][:, 0].any()


def test_star_row_bucket_compiles_one_program():
    """Ticks of 9 to 16 star rows share the 16-row program; 17 rows is
    the next bucket."""
    eng = _engine("monotone", 2)
    g = eng.graph

    def tick(n_rows):
        sizes = [n_rows // 2, n_rows - n_rows // 2]
        qs = [random_connected_query(g, k, seed=100 + i) for i, k in enumerate(sizes)]
        assert sum(q.n_vertices for q in qs) == n_rows
        eng._query_node_embeddings_many(qs)

    _embed_query_stars.clear_cache()
    for n_rows in range(9, 17):
        tick(n_rows)
    assert _embed_query_stars._cache_size() == 1
    tick(17)
    assert _embed_query_stars._cache_size() == 2
