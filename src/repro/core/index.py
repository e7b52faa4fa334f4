"""Packed block forest — the TPU-native replacement for the aR*-tree (§4.2).

The paper stores path embeddings in an aggregate R*-tree and traverses it
best-first with a max-heap.  Pointer trees and heaps are hostile to the
TPU execution model, so we keep the *pruning mathematics* (Lemmas 4.1–4.4)
and replace the *control structure*:

  · paths are sorted by (label-embedding bytes, dominance-embedding Morton
    code) so neighbors in the order have tight bounding boxes;
  · consecutive runs of ``block_size`` paths form leaf blocks; each block
    stores min/max over o(p) (the MBR of Lemma 4.4), over o₀(p)
    (MBR₀ of Lemma 4.3) and over each of the n multi-GNN o'(p) (MBR');
  · ``fanout`` consecutive blocks form a level-1 super-block, and so on —
    a *packed forest* stored as dense (n_blocks, dim, 2) arrays per level;
  · a query runs level-synchronous masked scans: one vectorized
    compare-reduce per level, then a leaf scan restricted to surviving
    blocks.  The paper's L1-norm early-exit (Alg. 3 lines 11-12) becomes a
    per-block key cutoff predicate evaluated in the same pass.

Aggregates (MBR', MBR₀) are exactly the aR-tree "aggregate data" of §4.2.

Batched reference (§Perf D):  ``query_index_batch`` runs the whole
online filter for a *batch* of Q query paths at once:

  1. level-synchronous masks — ONE (Q, blocks, D) compare-reduce per
     level for every query simultaneously, descending through the union
     of surviving blocks while tracking per-query survival;
  2. a fused work-proportional leaf scan — the (query, row) pairs from
     each query's OWN surviving blocks pack into row-aligned arrays and
     one Pallas ``dominance_scan_pairs`` call (label + dominance +
     multi-GNN checks concatenated along features) decides every pair;
     the pure-NumPy reference stays behind ``use_pallas=False`` and is
     bit-equal (tests/test_batched_online.py).

The engine probes through the stacked-tensor index (core/stacked.py,
dist/probe.py); ``query_index_batch``/``query_index_batch_multi`` are
the per-partition reference it is held to bit for bit in the tests, and
the scalar ``query_index`` is the reference of those.

GNN-PGE two-level probe (§Perf E — this PR): with the
``PackedGroupIndex`` sidecar (core/grouping.py) attached,
``use_groups=True`` inserts a *group* level between the block descent
and the leaf scan — surviving blocks expand to their path groups, ONE
fused scan checks every (query, group) MBR pair, and only members of
surviving groups reach the exact leaf predicates.  Same match sets,
measurably fewer leaf-level dominance comparisons (``PAIR_COUNTERS``).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.obs.metrics import REGISTRY

__all__ = [
    "PackedIndex",
    "PackedGroupIndex",
    "build_index",
    "query_index",
    "query_index_batch",
    "query_index_batch_multi",
    "leaf_scan",
    "leaf_scan_batch",
    "reset_pair_counters",
]

# incremented on every fused Pallas leaf scan — lets integration tests prove
# the kernel runs on the engine's real query path (not just in kernel tests)
PALLAS_SCAN_CALLS = 0
# the most (query, row/group) pairs one such call has checked — shows a
# scan spanned more than one kernel block
PALLAS_SCAN_MAX_PAIRS = 0


def _count_pallas_scan(n_pairs: int) -> None:
    global PALLAS_SCAN_CALLS, PALLAS_SCAN_MAX_PAIRS
    PALLAS_SCAN_CALLS += 1
    PALLAS_SCAN_MAX_PAIRS = max(PALLAS_SCAN_MAX_PAIRS, int(n_pairs))

# (query, row) / (query, group) pairs issued by the batched probes since the
# last reset — tests use these to prove the two-level grouped probe issues
# measurably fewer leaf-level dominance comparisons (test_grouped_index.py).
# Backed by the obs registry (thread-safe: the engine executor thread, the
# compaction thread, and cluster host threads all probe concurrently);
# ``PAIR_COUNTERS`` below is a dict-like read/write view kept for
# compatibility with tests and dist/placement cost feeds.
_PAIR_METRIC = REGISTRY.counter(
    "gnnpe_probe_pairs_total",
    "Probe pairs issued since process start, by predicate level",
    labels=("kind",),
)
_LEAF_PAIRS = _PAIR_METRIC.labels(kind="leaf_pairs")
_GROUP_PAIRS = _PAIR_METRIC.labels(kind="group_pairs")
# (query path, group) pairs that pass the group-MBR check: the funnel's
# surviving_groups rung, counted by every grouped probe (stacked and reference)
_SURVIVING_GROUPS = _PAIR_METRIC.labels(kind="surviving_groups")
_PAIR_CHILDREN = {"leaf_pairs": _LEAF_PAIRS, "group_pairs": _GROUP_PAIRS}


class _PairCountersView:
    """Dict-compatible view over the registry pair counters.

    Supports the historical access patterns — ``PAIR_COUNTERS["leaf_pairs"]``,
    ``PAIR_COUNTERS["leaf_pairs"] += n``, ``dict(PAIR_COUNTERS)`` — while the
    authoritative (locked) values live in the obs registry.
    """

    __slots__ = ()

    def __getitem__(self, key: str) -> int:
        return int(_PAIR_CHILDREN[key].value)

    def __setitem__(self, key: str, value: int) -> None:
        child = _PAIR_CHILDREN[key]
        with child._lock:
            child.value = float(value)

    def __iter__(self):
        return iter(_PAIR_CHILDREN)

    def __len__(self) -> int:
        return len(_PAIR_CHILDREN)

    def __contains__(self, key: object) -> bool:
        return key in _PAIR_CHILDREN

    def keys(self):
        return _PAIR_CHILDREN.keys()

    def items(self):
        return [(k, int(c.value)) for k, c in _PAIR_CHILDREN.items()]

    def get(self, key: str, default: int = 0) -> int:
        return self[key] if key in _PAIR_CHILDREN else default

    def __eq__(self, other: object) -> bool:
        if isinstance(other, (dict, _PairCountersView)):
            other_items = other if isinstance(other, dict) else dict(other.items())
            return dict(self.items()) == other_items
        return NotImplemented

    def __repr__(self) -> str:
        return f"_PairCountersView({dict(self.items())!r})"


PAIR_COUNTERS = _PairCountersView()


def reset_pair_counters() -> "_PairCountersView":
    """Zero the probe pair counters; returns the compat view."""
    for child in _PAIR_CHILDREN.values():
        with child._lock:
            child.value = 0.0
    return PAIR_COUNTERS


def _morton_key(x: np.ndarray, bits: int = 8) -> np.ndarray:
    """Interleaved-bit (Morton) key over quantized embedding coords.

    Vectorized bit-interleave: for each of ``bits`` rounds (most-
    significant first) pack one bit from every dim into a d-wide chunk
    and shift it in — identical (mod 2⁶⁴) to the scalar bits×dims loop.
    """
    q = np.clip((x * (1 << bits)).astype(np.uint64), 0, (1 << bits) - 1)
    n, d = q.shape
    key = np.zeros(n, dtype=np.uint64)
    if d == 0 or n == 0:
        return key
    if d >= 64:  # chunk shift would overflow; keep the scalar fallback
        for b in range(bits - 1, -1, -1):
            for t in range(d):
                key = (key << np.uint64(1)) | ((q[:, t] >> np.uint64(b)) & np.uint64(1))
        return key
    place = (np.uint64(d - 1) - np.arange(d, dtype=np.uint64))[None, :]
    for b in range(bits - 1, -1, -1):
        chunk = ((q >> np.uint64(b)) & np.uint64(1)) << place
        key = (key << np.uint64(d)) | chunk.sum(axis=1, dtype=np.uint64)
    return key


_Q_SCALE = 250.0  # int8 grid over (0,1): data ceil / query floor (sound)


def quantize_data(x: np.ndarray) -> np.ndarray:
    """Conservative data-side int8: rounded UP (never under-reports)."""
    return np.clip(np.ceil(x * _Q_SCALE) - 125, -125, 126).astype(np.int8)


def quantize_query(x: np.ndarray) -> np.ndarray:
    """Conservative query-side int8: rounded DOWN.
    q ≤ e ⇒ floor(q·s) ≤ ceil(e·s) — no false dismissal; pruning fires only
    when floor(q·s) > ceil(e·s) ⇒ q > e — sound."""
    return np.clip(np.floor(x * _Q_SCALE) - 125, -125, 126).astype(np.int8)


def hash_labels(paths_labels: np.ndarray) -> np.ndarray:
    """Polynomial hash of the label sequence (equal seq ⇒ equal hash;
    differing hash ⇒ safe prune; collisions only add refine work)."""
    h = np.zeros(paths_labels.shape[0], np.int64)
    P = np.int64(1_000_003)
    for j in range(paths_labels.shape[1]):
        h = h * P + paths_labels[:, j].astype(np.int64) + 1
    return h


@dataclasses.dataclass
class PackedGroupIndex:
    """GNN-PGE sidecar: contiguous path bundles + per-group pruning bounds.

    Paths are already (label-embedding, Morton)-sorted by ``build_index``;
    a *group* is a contiguous run of ≤ ``group_size`` rows that never
    crosses a leaf-block boundary, so each leaf block owns an integral set
    of groups and the block-level descent composes with the group level.
    The sort *tends* to make groups label-homogeneous, but a group may
    straddle a label run — the probe therefore checks o₀(p_q) against the
    group's MBR₀ *interval* (never equality), keeping pruning sound for
    any group composition.  One dominance check against a group's upper
    bound (Lemma 4.4 at group granularity) prunes the whole bundle with
    no false dismissals; only members of surviving groups reach the
    leaf-level exact scan (see ``query_index_batch_multi(use_groups=True)``).

    Dominance pruning is one-sided (q ⪯ max), so only the upper bound is
    stored for the dominance embeddings; MBR₀ needs both ends for the
    containment test.
    """

    group_start: np.ndarray  # (G+1,) int64 row offsets in the sorted order
    mbr_hi: np.ndarray  # (G, Dcat) upper bound over concat(main, multi-GNN) embeddings
    mbr0: np.ndarray  # (G, D0, 2) lo/hi over the label embeddings o₀
    block_group_start: np.ndarray  # (n_blocks+1,) int64 — groups per leaf block
    group_size: int  # configured max members per group

    @property
    def n_groups(self) -> int:
        return int(self.group_start.shape[0]) - 1

    def member_counts(self) -> np.ndarray:
        return np.diff(self.group_start)

    def nbytes(self) -> int:
        return int(
            self.group_start.nbytes
            + self.mbr_hi.nbytes
            + self.mbr0.nbytes
            + self.block_group_start.nbytes
        )

    def stats(self) -> dict:
        counts = self.member_counts()
        return {
            "n_groups": self.n_groups,
            "group_size": int(self.group_size),
            "mean_members": float(counts.mean()) if counts.size else 0.0,
            "max_members": int(counts.max()) if counts.size else 0,
            "group_bytes": self.nbytes(),
        }


@dataclasses.dataclass
class PackedIndex:
    """Per-partition index over paths of one length."""

    paths: np.ndarray  # (P, l+1) int32 vertex ids, sorted order
    emb: np.ndarray  # (P, D) float32  — o(p), D = (l+1)·d
    emb0: np.ndarray  # (P, D) float32  — o₀(p) label embedding
    emb_multi: np.ndarray  # (n_gnn, P, D) float32 — o'(p) per extra GNN
    # per level: (n_blocks, D, 2) min/max over emb; same for emb0/emb_multi
    levels: list  # list of dicts {mbr, mbr0, mbr_multi, key_max, start, count}
    block_size: int
    fanout: int
    # §Perf C1/C2 (beyond-paper): conservative int8 leaf pre-filter + 8-byte
    # label hashes — ~4× less leaf-scan traffic, exactness preserved by the
    # exact check on pre-filter survivors (see tests/test_quantized_index.py)
    emb_q: np.ndarray | None = None  # (P, D·(1+n)) int8, concat main+multi
    label_hash: np.ndarray | None = None  # (P,) int64
    # GNN-PGE group sidecar (core/grouping.py attaches it); None = per-path only
    groups: PackedGroupIndex | None = None

    @property
    def n_paths(self) -> int:
        return int(self.paths.shape[0])

    def nbytes(self) -> int:
        total = self.paths.nbytes + self.emb.nbytes + self.emb0.nbytes + self.emb_multi.nbytes
        for lv in self.levels:
            total += lv["mbr"].nbytes + lv["mbr0"].nbytes + lv["mbr_multi"].nbytes
        # quantized sidecars are real index bytes too (offline_stats parity)
        if self.emb_q is not None:
            total += self.emb_q.nbytes
        if self.label_hash is not None:
            total += self.label_hash.nbytes
        if self.groups is not None:
            total += self.groups.nbytes()
        return total


def _build_level(emb, emb0, emb_multi, group: int):
    P = emb.shape[0]
    nb = (P + group - 1) // group
    pad = nb * group - P

    def mm(x):
        if pad:
            lo = np.concatenate([x, np.full((pad, x.shape[1]), np.inf, x.dtype)])
            hi = np.concatenate([x, np.full((pad, x.shape[1]), -np.inf, x.dtype)])
        else:
            lo = hi = x
        lo = lo.reshape(nb, group, -1).min(axis=1)
        hi = hi.reshape(nb, group, -1).max(axis=1)
        return np.stack([lo, hi], axis=-1)  # (nb, D, 2)

    mbr = mm(emb)
    mbr0 = mm(emb0)
    mbr_multi = np.stack([mm(e) for e in emb_multi], axis=0) if emb_multi.shape[0] else np.zeros((0, nb, emb.shape[1], 2), np.float32)
    return {"mbr": mbr, "mbr0": mbr0, "mbr_multi": mbr_multi}


def build_index(
    paths: np.ndarray,
    emb: np.ndarray,
    emb0: np.ndarray,
    emb_multi: np.ndarray | None = None,
    block_size: int = 128,
    fanout: int = 16,
    quantize: bool = False,
    path_labels: np.ndarray | None = None,
) -> PackedIndex:
    P = paths.shape[0]
    D = emb.shape[1] if P else 0
    if emb_multi is None:
        emb_multi = np.zeros((0, P, D), np.float32)
    if P == 0:
        return PackedIndex(paths, emb.astype(np.float32), emb0.astype(np.float32), emb_multi.astype(np.float32), [], block_size, fanout)
    # sort: label-embedding lexicographic first (tight MBR₀ per block —
    # most blocks hold a single label sequence), Morton key within.
    lab_keys = np.ascontiguousarray(emb0).view([("", emb0.dtype)] * emb0.shape[1]).ravel()
    morton = _morton_key(emb)
    order = np.lexsort((morton, lab_keys))
    paths = np.ascontiguousarray(paths[order])
    emb = np.ascontiguousarray(emb[order]).astype(np.float32)
    emb0 = np.ascontiguousarray(emb0[order]).astype(np.float32)
    emb_multi = np.ascontiguousarray(emb_multi[:, order]).astype(np.float32)

    levels = [_build_level(emb, emb0, emb_multi, block_size)]
    while levels[-1]["mbr"].shape[0] > fanout:
        top = levels[-1]
        nb = top["mbr"].shape[0]
        grp = fanout
        n_sup = (nb + grp - 1) // grp
        pad = n_sup * grp - nb

        def roll(x):
            if pad:
                fill_lo = np.full((pad,) + x.shape[1:], np.inf, x.dtype)
                fill_hi = np.full((pad,) + x.shape[1:], -np.inf, x.dtype)
                lo = np.concatenate([x, fill_lo])[:, :, 0].reshape(n_sup, grp, -1).min(axis=1)
                hi = np.concatenate([x, fill_hi])[:, :, 1].reshape(n_sup, grp, -1).max(axis=1)
            else:
                lo = x[:, :, 0].reshape(n_sup, grp, -1).min(axis=1)
                hi = x[:, :, 1].reshape(n_sup, grp, -1).max(axis=1)
            return np.stack([lo, hi], axis=-1)

        lvl = {
            "mbr": roll(top["mbr"]),
            "mbr0": roll(top["mbr0"]),
            "mbr_multi": np.stack([roll(m) for m in top["mbr_multi"]], axis=0)
            if top["mbr_multi"].shape[0]
            else np.zeros((0, n_sup, top["mbr"].shape[1], 2), np.float32),
        }
        levels.append(lvl)
    idx = PackedIndex(paths, emb, emb0, emb_multi, levels, block_size, fanout)
    if quantize:
        cat = np.concatenate([emb] + [m for m in emb_multi], axis=1) if emb_multi.shape[0] else emb
        idx.emb_q = quantize_data(cat)
        if path_labels is not None:
            idx.label_hash = hash_labels(path_labels[order])
    return idx


# --------------------------------------------------------------------------
# Query-side pruning (Lemmas 4.1–4.4), level-synchronous
# --------------------------------------------------------------------------


def _block_mask(level, q_emb, q_emb0, q_multi, eps: float):
    """Survival mask over one level's blocks for one query path."""
    mbr, mbr0 = level["mbr"], level["mbr0"]
    # Lemma 4.3: o₀(p_q) ∈ MBR₀ (with fp tolerance)
    m_label = np.all((q_emb0 >= mbr0[:, :, 0] - eps) & (q_emb0 <= mbr0[:, :, 1] + eps), axis=1)
    # Lemma 4.4: DR(o(p_q)) ∩ MBR ≠ ∅  ⇔  ∀t  o(p_q)[t] ≤ MBR_max[t]
    m_dom = np.all(q_emb <= mbr[:, :, 1] + eps, axis=1)
    mask = m_label & m_dom
    for i in range(q_multi.shape[0]):
        mask &= np.all(q_multi[i] <= level["mbr_multi"][i][:, :, 1] + eps, axis=1)
    return mask


def leaf_scan(
    index: PackedIndex, block_ids: np.ndarray, q_emb, q_emb0, q_multi, eps: float,
    q_label_hash: int | None = None,
):
    """Lemmas 4.1 + 4.2 over candidate leaf blocks → path row indices.

    When the index carries the int8/hashed sidecar (§Perf C1/C2), a
    conservative pre-filter touches only 26 B/path instead of 96 B/path;
    the exact predicates run on the (tiny) survivor set — same result.
    """
    if index.n_paths == 0 or block_ids.size == 0:
        return np.zeros((0,), np.int64)
    bs = index.block_size
    rows = (block_ids[:, None] * bs + np.arange(bs)[None, :]).reshape(-1)
    rows = rows[rows < index.n_paths]
    if index.emb_q is not None:
        qcat = np.concatenate([q_emb] + [q_multi[i] for i in range(q_multi.shape[0])])
        qq = quantize_query(qcat)
        pre = np.all(qq[None, :] <= index.emb_q[rows], axis=1)
        if index.label_hash is not None and q_label_hash is not None:
            pre &= index.label_hash[rows] == q_label_hash
        rows = rows[pre]
        if rows.size == 0:
            return rows
    emb = index.emb[rows]
    emb0 = index.emb0[rows]
    # Lemma 4.1: label embedding equality
    ok = np.all(np.abs(emb0 - q_emb0) <= eps, axis=1)
    # Lemma 4.2: o(p_q) ⪯ o(p_z)
    ok &= np.all(q_emb <= emb + eps, axis=1)
    for i in range(q_multi.shape[0]):
        ok &= np.all(q_multi[i] <= index.emb_multi[i][rows] + eps, axis=1)
    return rows[ok]


def query_index(
    index: PackedIndex,
    q_emb: np.ndarray,
    q_emb0: np.ndarray,
    q_multi: np.ndarray | None = None,
    eps: float = 1e-6,
    return_stats: bool = False,
    q_label_hash: int | None = None,
):
    """Retrieve candidate path rows for one query path (Alg. 3 traversal).

    Level-synchronous: start from the top level, AND each level's block
    survival mask down to the leaves, then run the fused leaf scan.
    """
    if q_multi is None:
        q_multi = np.zeros((index.emb_multi.shape[0], q_emb.shape[0]), np.float32)
    if index.n_paths == 0:
        empty = np.zeros((0,), np.int64)
        return (empty, {"scanned_blocks": 0, "scanned_paths": 0}) if return_stats else empty
    n_levels = len(index.levels)
    # top level: scan all its blocks
    survivors = None  # block ids at current level
    for li in range(n_levels - 1, -1, -1):
        level = index.levels[li]
        nb = level["mbr"].shape[0]
        if survivors is None:
            cand = np.arange(nb)
        else:
            # children of surviving super-blocks
            cand = (survivors[:, None] * index.fanout + np.arange(index.fanout)[None, :]).reshape(-1)
            cand = cand[cand < nb]
        if cand.size == 0:
            empty = np.zeros((0,), np.int64)
            return (empty, {"scanned_blocks": 0, "scanned_paths": 0}) if return_stats else empty
        sub = {
            "mbr": level["mbr"][cand],
            "mbr0": level["mbr0"][cand],
            "mbr_multi": level["mbr_multi"][:, cand],
        }
        mask = _block_mask(sub, q_emb, q_emb0, q_multi, eps)
        survivors = cand[mask]
    rows = leaf_scan(index, survivors, q_emb, q_emb0, q_multi, eps, q_label_hash)
    if return_stats:
        stats = {
            "scanned_blocks": int(survivors.size),
            "scanned_paths": int(survivors.size) * index.block_size,
        }
        return rows, stats
    return rows


# --------------------------------------------------------------------------
# Batched query path (§Perf D): Q query paths per traversal, fused leaf scan
# --------------------------------------------------------------------------


def _block_mask_batch(mbr, mbr0, mbr_multi, q_emb, q_emb0, q_multi, eps: float):
    """(Q, C) survival mask over C blocks for Q queries — one compare-reduce.

    Same Lemma 4.3/4.4 predicates as ``_block_mask``, broadcast over the
    query axis instead of looped over queries.
    """
    m = np.all(
        (q_emb0[:, None, :] >= mbr0[None, :, :, 0] - eps)
        & (q_emb0[:, None, :] <= mbr0[None, :, :, 1] + eps),
        axis=2,
    )
    m &= np.all(q_emb[:, None, :] <= mbr[None, :, :, 1] + eps, axis=2)
    for i in range(q_multi.shape[0]):
        m &= np.all(q_multi[i][:, None, :] <= mbr_multi[i][None, :, :, 1] + eps, axis=2)
    return m


def _descend_batch(index: PackedIndex, q_emb, q_emb0, q_multi, eps: float):
    """Level-synchronous descent for a query batch → (cand, alive).

    ``cand`` is the union of leaf blocks surviving for ANY query;
    ``alive[(qi, ci)]`` says whether leaf block ``cand[ci]`` survives for
    query ``qi`` — each level is ONE (Q, blocks, D) compare-reduce.
    """
    Q = q_emb.shape[0]
    cand = None
    alive = None
    for li in range(len(index.levels) - 1, -1, -1):
        level = index.levels[li]
        nb = level["mbr"].shape[0]
        if cand is None:
            cand = np.arange(nb)
            alive = np.ones((Q, nb), bool)
        else:
            fo = index.fanout
            children = (cand[:, None] * fo + np.arange(fo)[None, :]).reshape(-1)
            alive = np.repeat(alive, fo, axis=1)
            valid = children < nb
            cand = children[valid]
            alive = alive[:, valid]
        if cand.size == 0:
            break
        alive &= _block_mask_batch(
            level["mbr"][cand],
            level["mbr0"][cand],
            level["mbr_multi"][:, cand],
            q_emb,
            q_emb0,
            q_multi,
            eps,
        )
        keep_cols = alive.any(axis=0)
        cand = cand[keep_cols]
        alive = alive[:, keep_cols]
    if cand is None:
        cand = np.zeros((0,), np.int64)
        alive = np.zeros((Q, 0), bool)
    return cand, alive


def _prefilter_pairs(index: PackedIndex, rows, q_ids, q_emb, q_multi, q_label_hash):
    """§Perf C1/C2 conservative int8 + label-hash pre-filter on (q, row) pairs."""
    if index.emb_q is None or rows.size == 0:
        return rows, q_ids
    n_gnn = q_multi.shape[0]
    qcat = np.concatenate([q_emb] + [q_multi[i] for i in range(n_gnn)], axis=1)
    qq = quantize_query(qcat)
    pre = np.all(qq[q_ids] <= index.emb_q[rows], axis=1)
    if index.label_hash is not None and q_label_hash is not None:
        pre &= index.label_hash[rows] == np.asarray(q_label_hash)[q_ids]
    return rows[pre], q_ids[pre]


def _pack_leaf_pairs(
    index: PackedIndex,
    cand: np.ndarray,
    alive: np.ndarray,
    q_emb,
    q_multi,
    q_label_hash,
):
    """(query, block) survivors → packed (rows, q_ids) leaf pairs.

    Applies the §Perf C1/C2 int8 + label-hash pre-filter when the index
    carries the sidecar.  ``q_ids`` is qi-major (sorted), so per-query
    splits downstream are one bincount + split.
    """
    bs = index.block_size
    qi_pair, ci_pair = np.nonzero(alive)  # qi-major order
    if qi_pair.size == 0:
        return np.zeros((0,), np.int64), np.zeros((0,), np.int64)
    row_mat = cand[ci_pair][:, None] * bs + np.arange(bs)[None, :]
    valid = row_mat < index.n_paths
    rows = row_mat[valid].astype(np.int64)
    q_ids = np.repeat(qi_pair, bs).reshape(-1, bs)[valid].astype(np.int64)
    _LEAF_PAIRS.inc(int(rows.size))
    rows, q_ids = _prefilter_pairs(index, rows, q_ids, q_emb, q_multi, q_label_hash)
    return rows, q_ids


def _gather_pair_operands(index: PackedIndex, rows, q_ids, q_emb, q_emb0, q_multi):
    """Row-aligned kernel operands for packed (query, row) pairs."""
    n_gnn = q_multi.shape[0]
    e_cat = (
        np.concatenate([index.emb[rows]] + [index.emb_multi[i][rows] for i in range(n_gnn)], axis=1)
        if n_gnn
        else index.emb[rows]
    )
    q_cat = (
        np.concatenate([q_emb] + [q_multi[i] for i in range(n_gnn)], axis=1)
        if n_gnn
        else q_emb
    )
    return q_cat[q_ids], q_emb0[q_ids], e_cat, index.emb0[rows]


def _pairs_keep_mask(qg, q0g, eg, e0g, eps: float, use_pallas: bool) -> np.ndarray:
    """Fused Lemma 4.1 + 4.2 verdict for row-aligned pairs."""
    if qg.shape[0] == 0:
        return np.zeros((0,), bool)
    if use_pallas:
        from ..kernels.dominance_scan.ops import dominance_scan_pairs

        _count_pallas_scan(qg.shape[0])
        return np.asarray(dominance_scan_pairs(qg, q0g, eg, e0g, eps=eps)).astype(bool)
    # NumPy reference (bit-equal): one row-aligned compare-reduce
    keep = np.all(qg <= eg + eps, axis=1)
    keep &= np.all(np.abs(e0g - q0g) <= eps, axis=1)
    return keep


def _pairs_keep_mask_numpy_lazy(index, rows, q_ids, q_emb, q_emb0, q_multi, eps):
    """NumPy pair verdict with label short-circuit (same result as the
    fused kernel): Lemma 4.1 equality first over the cheap (T, d) label
    columns — only its (rare) survivors pay the wider dominance gather.
    """
    lab = np.all(np.abs(index.emb0[rows] - q_emb0[q_ids]) <= eps, axis=1)
    sub = np.nonzero(lab)[0]
    if sub.size == 0:
        return lab
    r = rows[sub]
    qsub = q_ids[sub]
    n_gnn = q_multi.shape[0]
    dom = np.all(q_emb[qsub] <= index.emb[r] + eps, axis=1)
    for i in range(n_gnn):
        dom &= np.all(q_multi[i][qsub] <= index.emb_multi[i][r] + eps, axis=1)
    keep = lab
    keep[sub] = dom
    return keep


def _split_rows(rows, q_ids, keep, Q: int) -> list:
    rows = rows[keep]
    counts = np.bincount(q_ids[keep], minlength=Q)
    return np.split(rows.astype(np.int64), np.cumsum(counts)[:-1])


# --------------------------------------------------------------------------
# GNN-PGE two-level probe: group-bound scan → member scan (surviving groups)
# --------------------------------------------------------------------------


def _expand_segments(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Concatenate the ranges [starts[i], starts[i]+counts[i]) — vectorized."""
    total = int(counts.sum())
    if total == 0:
        return np.zeros((0,), np.int64)
    ends = np.cumsum(counts)
    within = np.arange(total, dtype=np.int64) - np.repeat(ends - counts, counts)
    return np.repeat(starts, counts).astype(np.int64) + within


def _pack_group_pairs(groups: PackedGroupIndex, cand: np.ndarray, alive: np.ndarray):
    """(query, block) survivors → packed (g_ids, q_ids) group pairs.

    Groups nest inside leaf blocks (``block_group_start``), so each
    surviving (query, block) cell expands to exactly that block's groups;
    qi-major order is preserved for the downstream bincount/split.
    """
    qi_pair, ci_pair = np.nonzero(alive)  # qi-major order
    if qi_pair.size == 0:
        return np.zeros((0,), np.int64), np.zeros((0,), np.int64)
    blk = cand[ci_pair]
    bgs = groups.block_group_start
    counts = bgs[blk + 1] - bgs[blk]
    g_ids = _expand_segments(bgs[blk], counts)
    q_ids = np.repeat(qi_pair, counts).astype(np.int64)
    return g_ids, q_ids


def _gather_group_operands(groups: PackedGroupIndex, g_ids, q_ids, q_emb, q_emb0, q_multi):
    """Row-aligned group-level operands for packed (query, group) pairs."""
    n_gnn = q_multi.shape[0]
    q_cat = (
        np.concatenate([q_emb] + [q_multi[i] for i in range(n_gnn)], axis=1)
        if n_gnn
        else q_emb
    )
    return (
        q_cat[q_ids],
        q_emb0[q_ids],
        groups.mbr_hi[g_ids],  # dominance upper bounds (Lemma 4.4 per group)
        groups.mbr0[g_ids, :, 0],  # label MBR₀ lower
        groups.mbr0[g_ids, :, 1],  # label MBR₀ upper
    )


def _groups_keep_mask(qg, q0g, hi, lo0, hi0, eps: float, use_pallas: bool) -> np.ndarray:
    """Group-level verdict: q ⪯ MBR_max  ∧  o₀(p_q) ∈ MBR₀ (eps-widened).

    Conservative by construction: any member passing the exact leaf
    predicates forces its group to pass here, so no false dismissals.
    """
    if qg.shape[0] == 0:
        return np.zeros((0,), bool)
    if use_pallas:
        from ..kernels.dominance_scan.ops import dominance_scan_groups

        _count_pallas_scan(qg.shape[0])
        return np.asarray(dominance_scan_groups(qg, q0g, hi, lo0, hi0, eps=eps)).astype(bool)
    keep = np.all(qg <= hi + eps, axis=1)
    keep &= np.all(q0g <= hi0 + eps, axis=1)
    keep &= np.all(q0g >= lo0 - eps, axis=1)
    return keep


def _query_index_batch_multi_grouped(items, eps, return_stats, use_pallas):
    """GNN-PGE two-level probe over several partitions (``use_groups=True``).

    Level-synchronous block descent is shared with the per-path probe;
    then:

      1. group level — surviving blocks expand to their groups, and ONE
         fused ``dominance_scan_groups`` call (per-partition pairs
         concatenated) checks every (query, group) MBR pair;
      2. member level — packed (query, group, member) offsets expand only
         the surviving groups' rows, which run the existing exact pair
         scan (int8 pre-filter + one fused ``dominance_scan_pairs``).

    Returns exactly the rows of the per-path probe (group pruning is
    sound and the member predicates are unchanged), touching far fewer
    leaf pairs (``PAIR_COUNTERS``).
    """
    packs = []
    for index, q_emb, q_emb0, q_multi, q_label_hash in items:
        q_emb = np.asarray(q_emb, np.float32)
        q_emb0 = np.asarray(q_emb0, np.float32)
        Q = q_emb.shape[0]
        if q_multi is None:
            q_multi = np.zeros((index.emb_multi.shape[0], Q, q_emb.shape[1]), np.float32)
        if index.n_paths == 0 or Q == 0:
            packs.append({"Q": Q, "empty": True})
            continue
        if index.groups is None:
            raise ValueError(
                "use_groups=True needs the PackedGroupIndex sidecar — "
                "run core.grouping.attach_groups(index, group_size) first"
            )
        cand, alive = _descend_batch(index, q_emb, q_emb0, q_multi, eps)
        g_ids, q_ids_g = _pack_group_pairs(index.groups, cand, alive)
        _GROUP_PAIRS.inc(int(g_ids.size))
        packs.append(
            {
                "Q": Q, "empty": False, "alive": alive, "index": index,
                "g_ids": g_ids, "q_ids_g": q_ids_g, "bs": index.block_size,
                "query": (q_emb, q_emb0, q_multi, q_label_hash),
                "g_ops": _gather_group_operands(
                    index.groups, g_ids, q_ids_g, q_emb, q_emb0, q_multi
                ),
            }
        )
    # ---- level 1: one fused group-bound scan across every partition ------
    live = [p for p in packs if not p["empty"] and p["g_ids"].size]
    if use_pallas and live:
        cat = [np.concatenate([p["g_ops"][k] for p in live]) for k in range(5)]
        keep_all = _groups_keep_mask(*cat, eps, use_pallas=True)
        offs = np.cumsum([0] + [p["g_ids"].size for p in live])
        for p, a, b in zip(live, offs[:-1], offs[1:]):
            p["g_keep"] = keep_all[a:b]
    else:
        for p in live:
            p["g_keep"] = _groups_keep_mask(*p["g_ops"], eps, use_pallas=False)
    # ---- level 2: member rows of surviving groups only -------------------
    for p in packs:
        if p["empty"]:
            continue
        index = p["index"]
        q_emb, q_emb0, q_multi, q_label_hash = p["query"]
        Q = p["Q"]
        g_keep = p.get("g_keep", np.zeros((0,), bool))
        g_surv = p["g_ids"][g_keep]
        q_surv = p["q_ids_g"][g_keep]
        gs = index.groups.group_start
        counts = gs[g_surv + 1] - gs[g_surv]
        rows = _expand_segments(gs[g_surv], counts)
        q_ids = np.repeat(q_surv, counts).astype(np.int64)
        _LEAF_PAIRS.inc(int(rows.size))
        _SURVIVING_GROUPS.inc(int(g_surv.size))
        p["checked_groups"] = np.bincount(p["q_ids_g"], minlength=Q)
        p["surviving_groups"] = np.bincount(q_surv, minlength=Q)
        p["member_rows"] = np.bincount(q_ids, minlength=Q)
        rows, q_ids = _prefilter_pairs(index, rows, q_ids, q_emb, q_multi, q_label_hash)
        p["rows"], p["q_ids"] = rows, q_ids
        if use_pallas:
            p["ops"] = _gather_pair_operands(index, rows, q_ids, q_emb, q_emb0, q_multi)
        else:
            p["keep"] = _pairs_keep_mask_numpy_lazy(
                index, rows, q_ids, q_emb, q_emb0, q_multi, eps
            )
    if use_pallas:
        # one fused exact member scan across every partition's pairs
        live = [p for p in packs if not p["empty"] and p["rows"].size]
        if live:
            qg = np.concatenate([p["ops"][0] for p in live])
            q0g = np.concatenate([p["ops"][1] for p in live])
            eg = np.concatenate([p["ops"][2] for p in live])
            e0g = np.concatenate([p["ops"][3] for p in live])
            keep_all = _pairs_keep_mask(qg, q0g, eg, e0g, eps, use_pallas=True)
            offs = np.cumsum([0] + [p["rows"].size for p in live])
            for p, a, b in zip(live, offs[:-1], offs[1:]):
                p["keep"] = keep_all[a:b]
    results = []
    stats = [] if return_stats else None
    for p in packs:
        Q = p["Q"]
        if p["empty"]:
            results.append([np.zeros((0,), np.int64) for _ in range(Q)])
            if return_stats:
                stats.append(
                    [
                        {
                            "scanned_blocks": 0, "scanned_groups": 0,
                            "surviving_groups": 0, "scanned_paths": 0,
                        }
                        for _ in range(Q)
                    ]
                )
            continue
        keep = p.get("keep")
        if keep is None:  # pallas mode with zero pairs
            keep = np.zeros((0,), bool)
        results.append(_split_rows(p["rows"], p["q_ids"], keep, Q))
        if return_stats:
            scanned = np.asarray(p["alive"].sum(axis=1))
            stats.append(
                [
                    {
                        "scanned_blocks": int(scanned[qi]),
                        "scanned_groups": int(p["checked_groups"][qi]),
                        "surviving_groups": int(p["surviving_groups"][qi]),
                        "scanned_paths": int(p["member_rows"][qi]),
                    }
                    for qi in range(Q)
                ]
            )
    if return_stats:
        return results, stats
    return results


def leaf_scan_batch(
    index: PackedIndex,
    block_ids: np.ndarray,  # (C,) union of candidate leaf blocks
    alive: np.ndarray,  # (Q, C) per-query block survival
    q_emb: np.ndarray,  # (Q, D)
    q_emb0: np.ndarray,  # (Q, D)
    q_multi: np.ndarray,  # (n, Q, D)
    eps: float,
    q_label_hash: np.ndarray | None = None,  # (Q,) int64
    use_pallas: bool = True,
) -> list:
    """Fused Lemmas 4.1 + 4.2 for a query batch — work-proportional.

    Each query contributes only the leaf rows of its OWN surviving
    blocks (a dense query×union scan would do Q×N work while per-query
    pruning leaves ≪ N rows alive).  The (query, row) pairs pack into
    row-aligned arrays and ONE Pallas ``dominance_scan_pairs`` call
    checks label + dominance + multi-GNN (features concatenated) for
    every pair: T = Σ_q rows_q — exactly the rows Q separate traversals
    would touch, in one streaming pass.  ``use_pallas=False`` runs the
    bit-equal NumPy reference.
    """
    Q = q_emb.shape[0]
    if index.n_paths == 0 or block_ids.size == 0 or Q == 0:
        return [np.zeros((0,), np.int64) for _ in range(Q)]
    rows, q_ids = _pack_leaf_pairs(index, block_ids, alive, q_emb, q_multi, q_label_hash)
    qg, q0g, eg, e0g = _gather_pair_operands(index, rows, q_ids, q_emb, q_emb0, q_multi)
    keep = _pairs_keep_mask(qg, q0g, eg, e0g, eps, use_pallas)
    return _split_rows(rows, q_ids, keep, Q)


def query_index_batch(
    index: PackedIndex,
    q_emb: np.ndarray,  # (Q, D)
    q_emb0: np.ndarray,  # (Q, D)
    q_multi: np.ndarray | None = None,  # (n, Q, D)
    eps: float = 1e-6,
    return_stats: bool = False,
    q_label_hash: np.ndarray | None = None,  # (Q,) int64
    use_pallas: bool = True,
    use_groups: bool = False,
):
    """Alg. 3 traversal for a BATCH of query paths — one pass per level.
    A test reference: the engine's online path is the stacked probe
    (dist/probe.py), which must return these rows bit for bit.

    Level-synchronous over the union frontier: at each level the blocks
    surviving for any query are expanded once, and a single (Q, blocks)
    compare-reduce updates every query's survival mask.  The leaf scan is
    one fused kernel call (see ``leaf_scan_batch``).  Per-query results
    are identical to Q separate ``query_index`` calls.

    ``use_groups=True`` routes through the GNN-PGE two-level probe
    (requires the ``PackedGroupIndex`` sidecar); row sets are identical.

    Returns a list of Q int64 row arrays (and per-query stats dicts when
    ``return_stats``).
    """
    out = query_index_batch_multi(
        [(index, q_emb, q_emb0, q_multi, q_label_hash)],
        eps=eps,
        return_stats=return_stats,
        use_pallas=use_pallas,
        use_groups=use_groups,
    )
    if return_stats:
        return out[0][0], out[1][0]
    return out[0]


def query_index_batch_multi(
    items: list,
    eps: float = 1e-6,
    return_stats: bool = False,
    use_pallas: bool = True,
    use_groups: bool = False,
):
    """Batched traversal over SEVERAL indexes (partitions) at once.
    The per-partition reference that ``StackedProbe.probe`` and
    ``probe_device`` are held to bit for bit in the tests; the engine's
    online path does not call it.

    ``items``: list of ``(index, q_emb, q_emb0, q_multi, q_label_hash)``
    — one entry per partition, each with its own (Q_i, D) query batch.
    The per-partition descents run level-synchronously; the packed leaf
    pairs of ALL partitions concatenate into ONE fused Pallas
    ``dominance_scan_pairs`` call (partitions share D, so their pair
    rows stack), amortizing the kernel dispatch across the entire
    multi-partition probe.  Returns a list (per item) of lists (per
    query) of row arrays; with ``return_stats``, also per-item per-query
    stats dicts.

    ``use_groups=True`` runs the GNN-PGE two-level probe instead
    (group-bound scan, then member scan on surviving groups) — same row
    sets, far fewer leaf pairs; every index needs the group sidecar.
    """
    if use_groups:
        return _query_index_batch_multi_grouped(items, eps, return_stats, use_pallas)
    packs = []
    for index, q_emb, q_emb0, q_multi, q_label_hash in items:
        q_emb = np.asarray(q_emb, np.float32)
        q_emb0 = np.asarray(q_emb0, np.float32)
        Q = q_emb.shape[0]
        if q_multi is None:
            q_multi = np.zeros((index.emb_multi.shape[0], Q, q_emb.shape[1]), np.float32)
        if index.n_paths == 0 or Q == 0:
            packs.append({"Q": Q, "empty": True})
            continue
        cand, alive = _descend_batch(index, q_emb, q_emb0, q_multi, eps)
        rows, q_ids = _pack_leaf_pairs(index, cand, alive, q_emb, q_multi, q_label_hash)
        pack = {
            "Q": Q, "empty": False, "alive": alive, "rows": rows, "q_ids": q_ids,
            "bs": index.block_size,
        }
        if use_pallas:
            pack["ops"] = _gather_pair_operands(index, rows, q_ids, q_emb, q_emb0, q_multi)
        else:
            # NumPy mode: verdicts per pack with the label short-circuit —
            # no cross-partition concat copies, no wide gather for pairs
            # the label check already rejects
            pack["keep"] = _pairs_keep_mask_numpy_lazy(
                index, rows, q_ids, q_emb, q_emb0, q_multi, eps
            )
        packs.append(pack)
    if use_pallas:
        # ONE fused kernel call across every partition's pairs
        live = [p for p in packs if not p["empty"] and p["rows"].size]
        if live:
            qg = np.concatenate([p["ops"][0] for p in live])
            q0g = np.concatenate([p["ops"][1] for p in live])
            eg = np.concatenate([p["ops"][2] for p in live])
            e0g = np.concatenate([p["ops"][3] for p in live])
            keep_all = _pairs_keep_mask(qg, q0g, eg, e0g, eps, use_pallas=True)
            offs = np.cumsum([0] + [p["rows"].size for p in live])
            for p, a, b in zip(live, offs[:-1], offs[1:]):
                p["keep"] = keep_all[a:b]
    results = []
    stats = [] if return_stats else None
    for p in packs:
        Q = p["Q"]
        if p["empty"]:
            results.append([np.zeros((0,), np.int64) for _ in range(Q)])
            if return_stats:
                stats.append([{"scanned_blocks": 0, "scanned_paths": 0} for _ in range(Q)])
            continue
        keep = p.get("keep")
        if keep is None:  # pallas mode with zero pairs
            keep = np.zeros((0,), bool)
        results.append(_split_rows(p["rows"], p["q_ids"], keep, Q))
        if return_stats:
            scanned = np.asarray(p["alive"].sum(axis=1))
            stats.append(
                [
                    {
                        "scanned_blocks": int(scanned[qi]),
                        "scanned_paths": int(scanned[qi]) * p["bs"],
                    }
                    for qi in range(Q)
                ]
            )
    if return_stats:
        return results, stats
    return results
