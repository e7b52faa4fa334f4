"""The plain reference: exact subgraph matching by backtracking.

Independent of the program: adjacency sets and a depth-first search, with
no index, no embedding and no planner.  A match maps query vertex ``i`` to
data vertex ``m[i]``: injective, label-preserving, and every query edge
lands on a data edge (non-induced: extra data edges are allowed).
"""
from __future__ import annotations

import numpy as np


class RefGraph:
    """The data graph as adjacency sets; applies edge updates in order."""

    def __init__(self, n: int, labels: np.ndarray, edges: np.ndarray):
        self.labels = np.asarray(labels).tolist()
        self.adj: list[set] = [set() for _ in range(n)]
        for u, v in np.asarray(edges).tolist():
            self.adj[u].add(v)
            self.adj[v].add(u)
        self.by_label: dict[int, list] = {}
        for v, lab in enumerate(self.labels):
            self.by_label.setdefault(lab, []).append(v)

    def apply(self, add: np.ndarray, remove: np.ndarray) -> None:
        """One update batch, as one step: present edges among ``remove``
        go, then absent edges among ``add`` (absent before the batch)
        come."""
        was = {(u, v) for u, v in np.asarray(add).tolist() if v in self.adj[u]}
        for u, v in np.asarray(remove).tolist():
            self.adj[u].discard(v)
            self.adj[v].discard(u)
        for u, v in np.asarray(add).tolist():
            if (u, v) not in was and u != v:
                self.adj[u].add(v)
                self.adj[v].add(u)

    def edge_set(self) -> set:
        return {(u, v) for u, nb in enumerate(self.adj) for v in nb if u < v}

    def match(self, q_labels, q_edges) -> set:
        """Every embedding of the query, as tuples indexed by query vertex."""
        k = len(q_labels)
        q_labels = [int(x) for x in q_labels]
        q_adj = [set() for _ in range(k)]
        for u, v in np.asarray(q_edges).reshape(-1, 2).tolist():
            q_adj[u].add(v)
            q_adj[v].add(u)
        # the rarest label first, then always a vertex joined to the ones
        # placed so far (the query is connected), most back-edges first
        freq = [len(self.by_label.get(lab, ())) for lab in q_labels]
        order = [min(range(k), key=lambda i: (freq[i], -len(q_adj[i])))]
        while len(order) < k:
            placed = set(order)
            order.append(max(
                (i for i in range(k) if i not in placed and q_adj[i] & placed),
                key=lambda i: (len(q_adj[i] & placed), -freq[i]),
            ))
        back = [[j for j in order[:d] if j in q_adj[order[d]]] for d in range(k)]
        out: set = set()
        m = [-1] * k
        used: set = set()
        adj, labels = self.adj, self.labels

        def extend(d: int) -> None:
            if d == k:
                out.add(tuple(m))
                return
            u = order[d]
            if d == 0:
                cands = self.by_label.get(q_labels[u], ())
            else:
                b = back[d]
                cands = min((adj[m[j]] for j in b), key=len)
                cands = [c for c in cands if all(c in adj[m[j]] for j in b)]
            for c in cands:
                if labels[c] == q_labels[u] and c not in used:
                    m[u] = c
                    used.add(c)
                    extend(d + 1)
                    used.discard(c)
            m[u] = -1

        extend(0)
        return out
