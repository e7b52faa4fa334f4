"""Seeded generators: data graph, query pool, operation schedule, update stream.

These are the benchmark's own copies of the program's generators (the
Newman-Watts-Strogatz graph and the connected-query sampler of
``repro.graphs``), so that no change to the program can change the data it
is measured on.  Everything here is NumPy and Python; the harness turns the
arrays into the program's input types.
"""
from __future__ import annotations

import dataclasses

import numpy as np


def csr(n: int, edges: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Undirected CSR (offsets, sorted neighbour rows) of unique u < v edges."""
    both = np.concatenate([edges, edges[:, ::-1]], axis=0).astype(np.int64)
    order = np.lexsort((both[:, 1], both[:, 0]))
    both = both[order]
    offsets = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(both[:, 0], minlength=n), out=offsets[1:])
    return offsets, both[:, 1].astype(np.int32)


def unique_edges(n: int, edges: np.ndarray) -> np.ndarray:
    """Edges as sorted unique (u, v) rows with u < v, self loops dropped."""
    e = np.sort(np.asarray(edges, np.int64).reshape(-1, 2), axis=1)
    e = e[e[:, 0] != e[:, 1]]
    keys = np.unique(e[:, 0] * n + e[:, 1])
    return np.stack([keys // n, keys % n], axis=1)


@dataclasses.dataclass(frozen=True)
class DataGraph:
    n: int
    labels: np.ndarray  # (n,) int32
    edges: np.ndarray  # (m, 2) int64, u < v, sorted, unique

    def csr(self) -> tuple[np.ndarray, np.ndarray]:
        return csr(self.n, self.edges)


def data_graph(spec: dict, seed: int) -> DataGraph:
    """The deployment's data graph from its configuration and the seed.

    ``nws``: a ring lattice of ``k`` nearest neighbours plus
    Binomial(n·k/2, p) uniform shortcuts, no rewiring (Newman-Watts-
    Strogatz); labels uniform over ``labels`` values.
    """
    if spec["generator"] != "nws" or spec["label_dist"] != "uniform":
        raise ValueError(f"unknown graph generator {spec['generator']!r}/{spec['label_dist']!r}")
    n, k, p = int(spec["vertices"]), int(spec["k"]), float(spec["p"])
    rng = np.random.default_rng([seed, 0])
    half = k // 2
    src = np.repeat(np.arange(n, dtype=np.int64), half)
    dst = (src + np.tile(np.arange(1, half + 1, dtype=np.int64), n)) % n
    n_short = rng.binomial(src.size, p)
    short = rng.integers(0, n, size=(n_short, 2))
    edges = unique_edges(n, np.concatenate([np.stack([src, dst], axis=1), short]))
    labels = rng.integers(0, int(spec["labels"]), size=n).astype(np.int32)
    return DataGraph(n, labels, edges)


@dataclasses.dataclass(frozen=True)
class Query:
    labels: np.ndarray  # (k,) int32
    edges: np.ndarray  # (m, 2) int64, u < v

    @property
    def avg_degree(self) -> float:
        return 2.0 * len(self.edges) / len(self.labels)


def _spanning_sparsify(edges: np.ndarray, n: int, max_edges: int, rng) -> np.ndarray:
    """A random spanning tree of ``edges`` plus random extras, at most
    ``max_edges`` in all (the graph stays connected)."""
    perm = rng.permutation(len(edges))
    parent = list(range(n))

    def find(x):
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    tree, extra = [], []
    for i in perm:
        ru, rv = find(int(edges[i, 0])), find(int(edges[i, 1]))
        if ru != rv:
            parent[ru] = rv
            tree.append(i)
        else:
            extra.append(i)
    keep = sorted(tree + extra[: max(max_edges - len(tree), 0)])
    return edges[np.asarray(keep, np.int64)]


def sample_query(offsets, nbrs, labels, size: int, density: str, rng) -> Query:
    """One connected query of ``size`` vertices sampled from the data graph
    by random expansion (a start vertex, then a uniform pick from the
    frontier), as its induced subgraph.

    ``density`` follows Sun & Luo (SIGMOD 2020): ``dense`` has average
    degree > 3, ``sparse`` <= 3 (a denser sample keeps a random spanning
    tree plus random edges up to degree 3), ``any`` takes the sample as it
    is.  Every query has at least one embedding: the sample itself.
    """
    n = len(labels)
    for _ in range(10_000):
        start = int(rng.integers(0, n))
        chosen = [start]
        frontier = set(nbrs[offsets[start]:offsets[start + 1]].tolist())
        while len(chosen) < size and frontier:
            nxt = int(rng.choice(sorted(frontier)))
            chosen.append(nxt)
            frontier |= set(nbrs[offsets[nxt]:offsets[nxt + 1]].tolist())
            frontier -= set(chosen)
        if len(chosen) < size:
            continue
        pos = {v: i for i, v in enumerate(chosen)}
        e = [
            (pos[u], pos[int(w)])
            for u in chosen
            for w in nbrs[offsets[u]:offsets[u + 1]]
            if int(w) in pos and pos[u] < pos[int(w)]
        ]
        edges = np.asarray(sorted(e), np.int64).reshape(-1, 2)
        q_labels = labels[np.asarray(chosen)].astype(np.int32)
        if density == "dense" and 2 * len(edges) <= 3 * size:
            continue
        if density == "sparse" and 2 * len(edges) > 3 * size:
            edges = _spanning_sparsify(edges, size, (3 * size) // 2, rng)
        return Query(q_labels, edges)
    raise RuntimeError(f"no {density} query of {size} vertices found")


def query_pool(g: DataGraph, pool_spec: list, seed: int) -> list[Query]:
    """The traffic mix's query pool, in the order its ``pool`` lists."""
    offsets, nbrs = g.csr()
    rng = np.random.default_rng([seed, 1])
    return [
        sample_query(offsets, nbrs, g.labels, int(s["size"]), s["density"], rng)
        for s in pool_spec
        for _ in range(int(s["count"]))
    ]


@dataclasses.dataclass(frozen=True)
class Op:
    due_s: float  # offset from the window's start
    kind: str  # "match" | "update"
    index: int  # pool index (match) or update-stream index (update)


def schedule(rate: float, seconds: float, update_share: float, n_pool: int, seed: int) -> list:
    """Open-loop operations for one window of ``seconds`` at ``rate`` per second.

    Arrivals are Poisson with their gaps stratified: the n = rate·seconds
    gaps are the exponential distribution's quantiles at (i + 0.5)/n, in an
    order drawn from the seed.  Every seed so offers the same number of
    operations, the same set of gaps and the same mix (``update_share`` of
    them updates; each pool query equally often) in a different order.
    """
    n = max(int(round(rate * seconds)), 1)
    rng = np.random.default_rng([seed, 2])
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    rng.shuffle(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    n_upd = int(round(update_share * n))
    kinds = np.array(["update"] * n_upd + ["match"] * (n - n_upd))
    rng.shuffle(kinds)
    picks = np.resize(np.arange(n_pool), n - n_upd)
    rng.shuffle(picks)
    ops, qi, ui = [], 0, 0
    for t, kind in zip(due, kinds):
        if kind == "match":
            ops.append(Op(float(t), "match", int(picks[qi])))
            qi += 1
        else:
            ops.append(Op(float(t), "update", ui))
            ui += 1
    return ops


@dataclasses.dataclass(frozen=True)
class EdgeUpdate:
    add: np.ndarray  # (a, 2) int64, u < v, absent before the update
    remove: np.ndarray  # (r, 2) int64, u < v, present before the update


def update_stream(g: DataGraph, n_updates: int, inserts: int, deletes: int, seed: int) -> list:
    """``n_updates`` batches of edge inserts and deletes, uniform over the
    graph as it stands when each batch applies (Sun et al., VLDB 2022:
    insert/delete streams).  Inserts join two distinct vertices that are
    not adjacent; deletes remove present edges; no edge appears twice in
    one batch, so every edit of every batch takes effect."""
    rng = np.random.default_rng([seed, 3])
    n = g.n
    edge_list = list(map(tuple, g.edges.tolist()))  # present edges, any order
    where = {e: i for i, e in enumerate(edge_list)}
    out = []
    for _ in range(n_updates):
        add = set()
        while len(add) < inserts:
            u, v = sorted(int(x) for x in rng.integers(0, n, size=2))
            if u != v and (u, v) not in where:
                add.add((u, v))
        rem = set()
        while len(rem) < deletes:
            rem.add(edge_list[int(rng.integers(0, len(edge_list)))])
        for e in sorted(rem):  # swap-remove
            i, last = where.pop(e), edge_list.pop()
            if last != e:
                edge_list[i] = last
                where[last] = i
        for e in sorted(add):
            where[e] = len(edge_list)
            edge_list.append(e)
        out.append(EdgeUpdate(
            np.asarray(sorted(add), np.int64).reshape(-1, 2),
            np.asarray(sorted(rem), np.int64).reshape(-1, 2),
        ))
    return out
