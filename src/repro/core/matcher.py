"""Candidate assembly + refinement (paper Alg. 3 lines 29-30, §4.4).

Candidates per query path come back from the packed indexes; this module
joins them into full embeddings and verifies exactly.  The paper uses a
multi-way hash join; we use a vectorized sort/merge-style join over
key arrays (hash tables don't vectorize; sort-merge does — see DESIGN §6).

Two interchangeable implementations sit behind ``join_impl``:

  * ``"numpy"`` — the original host join: uint64 lex-keys, one argsort +
    searchsorted per step, vectorized flat-CSR refine.  This is the
    oracle every other path is tested against.
  * ``"device"`` — the same join as ONE jitted XLA computation per step
    over the ``kernels/merge_join`` op family: multi-word int32 keys
    (this build runs without x64), fused sort → run-bounds binary search
    → run-length pair expansion → injectivity filter (Pallas kernel on
    TPU) → keyed row dedup, all on pad-and-bucketed power-of-two row
    shapes so XLA retraces only per bucket.  The assembled table stays
    device-resident through a jitted CSR edge-membership refine (binary
    search over the cached (src, dst) edge tensors); only the final
    verified rows cross back to the host.  Candidate arrays may be NumPy
    (uploaded once) or already-device-resident ``(padded_rows, count)``
    pairs straight from the stacked probe (dist/probe.py) — the path
    that removes the per-batch device→host candidate round-trip.

Match SETS are identical between the two (tests compare them through
``sort_matches``); list order differs — the device join keeps its table
key-sorted, the host join keeps join order.
"""
from __future__ import annotations

import functools
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from ..graphs import Graph
from ..kernels.merge_join.ops import (
    dedup_mask,
    expand_pairs,
    injectivity_mask,
    lex_order,
    pack_words,
    run_lookup,
)
from ..shapes import pow2_at_least

__all__ = [
    "join_candidates",
    "refine",
    "match_from_candidates",
    "match_from_candidates_many",
    "sort_matches",
]


def sort_matches(matches: list) -> list:
    """Canonical (lexicographic) ordering of a match list.

    The match SET of an exact engine is deterministic, but the list
    order tracks the join's table order, which can differ between a
    delta-maintained index and a from-scratch rebuild (row ties resort)
    or between plans.  Update equivalence checks and the bench gate
    compare through this ordering."""
    return sorted(matches)


def _lex_keys(a: np.ndarray, n_values: int) -> np.ndarray:
    """Rows → ONE sortable key array preserving lexicographic row order.

    Bit-packs each row into a uint64 when ``cols · ceil(log2(n_values))``
    fits (always at paper path lengths); wider rows reinterpret their
    big-endian bytes as fixed-size void scalars, whose memcmp order is
    still lexicographic for non-negative ints.  Every sort/merge/dedup
    in the join then sorts one key column instead of lexsorting the row
    columns, and key equality is exact row equality (no hash aliasing —
    the old ``2³¹``-radix encode could wrap past 2 shared columns).
    """
    cols = a.shape[1]
    bits = max(int(np.ceil(np.log2(max(n_values, 2)))), 1)
    if cols * bits <= 63:
        k = np.zeros(a.shape[0], np.uint64)
        shift, mask = np.uint64(bits), np.uint64((1 << bits) - 1)
        for j in range(cols):
            k = (k << shift) | (a[:, j].astype(np.uint64) & mask)
        return k
    b = np.ascontiguousarray(a.astype(">i4"))
    return b.view(np.dtype((np.void, 4 * cols))).ravel()


def _unique_rows(a: np.ndarray, n_values: int) -> np.ndarray:
    """``np.unique(a, axis=0)`` (same rows, same order) via one key sort."""
    if a.shape[0] <= 1:
        return a
    keys = _lex_keys(a, n_values)
    order = np.argsort(keys, kind="stable")
    ks = keys[order]
    keep = np.ones(ks.size, bool)
    keep[1:] = ks[1:] != ks[:-1]
    return a[order[keep]]


def _join_pair(
    table: np.ndarray,
    table_cols: list[int],
    cand: np.ndarray,
    cand_cols: list[int],
    n_values: int,
    assume_unique: bool = False,
) -> tuple[np.ndarray, list[int]]:
    """Join a partial-assignment table with one path's candidate rows.

    table: (R, len(table_cols)) data-vertex assignments for query vertices
    ``table_cols``; cand: (C, len(cand_cols)) ditto.  Returns the merged
    table over the union of columns with key equality on shared columns
    and injectivity on the new columns.
    """
    shared = [c for c in cand_cols if c in table_cols]
    new_cols = [c for c in cand_cols if c not in table_cols]
    t_idx = [table_cols.index(c) for c in shared]
    c_idx = [cand_cols.index(c) for c in shared]
    n_idx = [cand_cols.index(c) for c in new_cols]

    if table.shape[0] == 0 or cand.shape[0] == 0:
        return np.zeros((0, len(table_cols) + len(new_cols)), np.int32), table_cols + new_cols

    if not shared:  # cartesian (paper joins connected paths, so rare)
        r = np.repeat(np.arange(table.shape[0]), cand.shape[0])
        c = np.tile(np.arange(cand.shape[0]), table.shape[0])
    else:
        # sort-merge join: pre-hashed single-key arrays (see _lex_keys)
        tk = _lex_keys(table[:, t_idx], n_values)
        ck = _lex_keys(cand[:, c_idx], n_values)
        order_t = np.argsort(tk, kind="stable")
        order_c = np.argsort(ck, kind="stable")
        tk_s, ck_s = tk[order_t], ck[order_c]
        # for each table row, locate the run of equal candidate keys
        lo = np.searchsorted(ck_s, tk_s, side="left")
        hi = np.searchsorted(ck_s, tk_s, side="right")
        reps = hi - lo
        r_s = np.repeat(np.arange(tk_s.shape[0]), reps)
        cum = np.cumsum(reps)
        starts = cum - reps
        pos = np.arange(int(cum[-1]) if reps.size else 0) - np.repeat(starts, reps)
        c_s = np.repeat(lo, reps) + pos
        r = order_t[r_s]
        c = order_c[c_s]

    merged = np.concatenate([table[r], cand[c][:, n_idx]], axis=1)
    # injectivity: new columns must not collide with existing assignments
    if n_idx:
        old_part = merged[:, : len(table_cols)]
        new_part = merged[:, len(table_cols):]
        ok = np.ones(merged.shape[0], bool)
        for j in range(new_part.shape[1]):
            ok &= ~np.any(old_part == new_part[:, j : j + 1], axis=1)
            for j2 in range(j + 1, new_part.shape[1]):
                ok &= new_part[:, j] != new_part[:, j2]
        merged = merged[ok]
    # dedup rows (different candidate paths can induce the same assignment).
    # With per-path candidates known duplicate-free (assume_unique — the
    # engine's partitions are root-disjoint and delta rows are disjoint
    # from main rows), a merged row determines its (table row, candidate
    # row) pair uniquely, so the table stays duplicate-free by induction
    # and the dedup sort is skipped.
    if not assume_unique and merged.shape[0] > 1:
        merged = _unique_rows(merged, n_values)
    return merged.astype(np.int32), table_cols + new_cols


def join_candidates(
    plan_paths: list,
    candidates: list,
    n_values: int | None = None,
    impl: str = "numpy",
    assume_unique: bool = False,
) -> tuple[np.ndarray, list[int]]:
    """Multi-way join of per-path candidates (smallest-first order).

    ``n_values`` bounds the vertex ids (``g.n_vertices``) so join keys
    bit-pack into uint64; derived from the data when omitted.
    ``impl="device"`` routes through the jitted merge-join pipeline and
    returns the (host-fetched) table — same row set.  ``assume_unique``
    promises each candidate array is duplicate-free (true for engine
    candidates), which keeps the tables duplicate-free by construction
    and skips every dedup sort — the device path's big win, since XLA's
    comparator sort is the one primitive slower than NumPy's.
    """
    if impl not in ("numpy", "device"):
        raise ValueError(f"unknown join impl {impl!r}; use 'numpy' or 'device'")
    if n_values is None:
        n_values = 2
        for c in candidates:  # (rows, count) pairs are device-resident
            rows, cnt = c if isinstance(c, tuple) else (c, None)
            rows = np.asarray(rows)[: cnt if cnt is not None else rows.shape[0]]
            if rows.size:
                n_values = max(n_values, int(rows.max()) + 1)
    if impl == "device":
        table, count, cols = _join_candidates_device(
            plan_paths, candidates, n_values, assume_unique=assume_unique
        )
        return np.asarray(table[:count]).astype(np.int32), cols
    order = np.argsort([c.shape[0] for c in candidates], kind="stable")
    first = int(order[0])
    table = candidates[first].astype(np.int32)
    if not assume_unique:
        table = _unique_rows(table, n_values).astype(np.int32)
    cols = list(plan_paths[first])
    # a path may repeat no vertices (simple), so cols are distinct per path
    # injectivity inside one path row:
    ok = np.ones(table.shape[0], bool)
    for a in range(table.shape[1]):
        for b in range(a + 1, table.shape[1]):
            ok &= table[:, a] != table[:, b]
    table = table[ok]
    remaining = [int(i) for i in order[1:]]
    # prefer joining paths that share columns with the current table
    while remaining:
        nxt = None
        for i in remaining:
            if set(plan_paths[i]) & set(cols):
                nxt = i
                break
        if nxt is None:
            nxt = remaining[0]
        remaining.remove(nxt)
        table, cols = _join_pair(
            table, cols, candidates[nxt], list(plan_paths[nxt]), n_values,
            assume_unique=assume_unique,
        )
        if table.shape[0] == 0:
            break
    return table, cols


_EDGE_KEY_CACHE: dict = {}  # id(graph) -> keys; evicted via weakref.finalize

# largest n for which src·n + dst stays below 2⁶³ for all src, dst < n —
# beyond it the packed int64 key silently wraps, so keys switch to a
# structured (src, dst) byte form whose memcmp order equals pair order
_EDGE_KEY_SAFE_N = int(np.int64(3_037_000_499))  # isqrt(2⁶³ − 1)


def _edge_key_arrays(src: np.ndarray, dst: np.ndarray, n_vertices: int) -> np.ndarray:
    """Sortable, equality-exact keys for directed edges (src, dst).

    ``src·n + dst`` packs into one int64 while ``n ≤ isqrt(2⁶³−1)``
    (every real graph); past that bound the product overflows int64 and
    two distinct edges could collide, so the keys fall back to big-endian
    (src, dst) void scalars — memcmp order == lexicographic pair order,
    and equality is exact at any ``n``.
    """
    if n_vertices <= _EDGE_KEY_SAFE_N:
        return src.astype(np.int64) * np.int64(n_vertices) + dst.astype(np.int64)
    b = np.ascontiguousarray(np.stack([src, dst], axis=1).astype(">i8"))
    return b.view(np.dtype((np.void, 16))).ravel()


def _edge_keys(g: Graph) -> np.ndarray:
    """Globally sorted edge keys of every directed CSR edge.

    CSR rows are grouped by ascending src and sorted within, so the flat
    key array is already sorted — one ``np.searchsorted`` over it answers
    edge membership for ALL candidate rows at once.  Graph-invariant, so
    cached per graph instance (refine runs once per query on the online
    hot path; rebuilding O(V+E) keys per query would dominate small
    candidate tables).
    """
    key = id(g)
    cached = _EDGE_KEY_CACHE.get(key)
    if cached is None:
        src = np.repeat(np.arange(g.n_vertices, dtype=np.int64), g.degrees)
        cached = _edge_key_arrays(src, g.nbrs.astype(np.int64), g.n_vertices)
        _EDGE_KEY_CACHE[key] = cached
        weakref.finalize(g, _EDGE_KEY_CACHE.pop, key, None)
    return cached


def _has_edges(keys: np.ndarray, n_vertices: int, du: np.ndarray, dv: np.ndarray) -> np.ndarray:
    """Vectorized membership: does G contain edge (du[i], dv[i]) ∀i."""
    if keys.size == 0 or du.size == 0:
        return np.zeros(du.shape[0], bool)
    want = _edge_key_arrays(du.astype(np.int64), dv.astype(np.int64), n_vertices)
    pos = np.searchsorted(keys, want)
    pos = np.minimum(pos, keys.size - 1)
    return keys[pos] == want


def refine(
    g: Graph,
    q: Graph,
    table: np.ndarray,
    cols: list[int],
    induced: bool = False,
    impl: str = "numpy",
) -> list[tuple[int, ...]]:
    """Exact verification of every assembled assignment (zero false positives).

    Edge checks are one flat-CSR ``searchsorted`` per query edge over all
    candidate rows (no per-row Python binary search) — see ``_edge_keys``.
    ``impl="device"`` runs the same checks as one jitted binary search
    over the cached device edge tensors (match set identical).
    """
    if impl == "device":
        rows = np.asarray(table, np.int32)
        return _refine_device(g, q, jnp.asarray(rows), rows.shape[0], cols, induced=induced)
    if table.shape[0] == 0:
        return []
    nq = q.n_vertices
    assert sorted(cols) == list(range(nq)), f"join must cover all query vertices, got {cols}"
    inv = np.argsort(np.asarray(cols))
    rows = table[:, inv]  # column j = data vertex for query vertex j
    ok = np.ones(rows.shape[0], bool)
    # label check (paths already enforce labels, but be defensive)
    for u in range(nq):
        ok &= g.labels[rows[:, u]] == q.labels[u]
    keys = _edge_keys(g)
    # every query edge must exist in G
    for u, v in q.edge_array():
        ok &= _has_edges(keys, g.n_vertices, rows[:, u], rows[:, v])
    if induced:
        # non-edges of q must be non-edges of G
        adj = q.adjacency_sets()
        for u in range(nq):
            for v in range(u + 1, nq):
                if v in adj[u]:
                    continue
                ok &= ~_has_edges(keys, g.n_vertices, rows[:, u], rows[:, v])
    # tolist() yields Python ints in one C pass — at match counts in the
    # 10⁵ range a per-element int() loop would dominate the whole refine
    return list(map(tuple, rows[ok].tolist()))


def match_from_candidates(
    g: Graph,
    q: Graph,
    plan_paths: list,
    candidates: list,
    induced: bool = False,
    join_impl: str = "numpy",
    assume_unique: bool = False,
) -> list[tuple[int, ...]]:
    """Join per-path candidates and verify exactly → the match list.

    ``join_impl="device"`` keeps the table on the accelerator end to end
    (join steps AND refine are jitted; candidates may already be device
    arrays); only the verified rows return to the host.  Match sets are
    identical to the NumPy path — list order differs (``sort_matches``
    canonicalizes).
    """
    if join_impl == "device":
        table, count, cols = _join_candidates_device(
            plan_paths, candidates, n_values=g.n_vertices, assume_unique=assume_unique
        )
        return _refine_device(g, q, table, count, cols, induced=induced)
    table, cols = join_candidates(
        plan_paths, candidates, n_values=g.n_vertices, assume_unique=assume_unique
    )
    return refine(g, q, table, cols, induced=induced)


# --------------------------------------------------------------------------
# Device join (§device-join PR): the same multi-way sort-merge join as a
# handful of jitted XLA computations over the kernels/merge_join ops.
#
# Shape discipline: every table/candidate tensor is padded to a power-of-
# two row bucket (like the delta star batches) so the jit cache holds one
# trace per (bucket, column signature) instead of one per candidate-set
# size.  Rows at index ≥ count carry the sentinel id ``n_values`` (tables)
# or ``n_values + 1`` (candidates): sentinels sort after every real key,
# can never equal one another across the two sides, and therefore probe
# empty runs — no validity masks cross the merge.  Only two small arrays
# sync to the host per join step (pair totals → output bucket, new row
# counts); tables never leave the device until refine's verdict.
#
# Batch axis: every step body is written per query and ``jax.vmap``-ed
# over a leading batch dim, so a whole tick of SAME-PLAN queries (the
# serving common case — ``match_from_candidates_many`` groups by plan
# signature) joins as ONE device program per step: dispatch overhead
# divides by the batch and XLA fuses across far larger loops.  The host
# join cannot batch — this is where the device path earns its speedup on
# join-heavy batches (benchmarks/bench_join.py).
# --------------------------------------------------------------------------


def _key_bits(n_values: int) -> int:
    """Bits per id column, covering the two pad sentinels too."""
    return max(int(np.ceil(np.log2(n_values + 2))), 1)


def _pad_rows(rows, cap: int):
    """(R, C) host or device rows → (cap, C) int32 device array (zero
    fill; every step re-sentinels its padding from the count)."""
    rows = jnp.asarray(rows, jnp.int32)
    if rows.shape[0] == cap:
        return rows
    if rows.shape[0] > cap:
        return rows[:cap]
    return jnp.pad(rows, ((0, cap - rows.shape[0]), (0, 0)))


def _stack_candidates(rows_list: list, counts: np.ndarray, cap: int, width: int):
    """Per-member candidate rows → ONE (B, cap, width) device array.

    All-host inputs assemble in NumPy and upload as a single transfer;
    any device-resident member (stacked-probe output) keeps the per-
    member eager pad/stack path instead of a round-trip through the
    host.  The batched join calls this once per plan path — without the
    single-upload fast path, B pads + a stack per step are the dominant
    dispatch overhead on small joins."""
    if all(isinstance(r, np.ndarray) for r in rows_list):
        out = np.zeros((len(rows_list), cap, width), np.int32)
        for b, r in enumerate(rows_list):
            n = min(int(counts[b]), cap)
            if n:
                out[b, :n] = r[:n]
        return jnp.asarray(out)
    return jnp.stack([_pad_rows(r, cap) for r in rows_list])


def _settle(merged, valid, bits: int, n_values: int, dedup: bool = True):
    """Shared join-step tail → ``(table, valid, count)``.

    Every invalid row is overwritten with the sentinel id (one fused
    elementwise ``where`` — never a scatter): sentinel rows probe empty
    runs in the next step and contribute zero pairs, so the table needs
    NO compaction between steps.  That matters because gather/scatter
    row-moves are the slowest primitives on XLA CPU — the join touches
    dropped rows only as cheap sentinel lanes instead of physically
    removing them.

    ``dedup=True`` (candidate arrays not promised duplicate-free)
    additionally drops duplicate rows via a keyed sort and compacts, so
    downstream caps stay tight in the one mode that can shrink tables.
    With ``assume_unique`` merged rows are already unique and the sort
    is skipped entirely."""
    merged = jnp.where(valid[:, None], merged, n_values)
    if dedup:
        order, keep = dedup_mask(pack_words(merged, bits), valid)
        out = merged[order][jnp.argsort(~keep, stable=True)]
        count = jnp.sum(keep)
        out = jnp.where((jnp.arange(out.shape[0]) < count)[:, None], out, n_values)
        return out, jnp.arange(out.shape[0]) < count, count
    return merged, valid, jnp.sum(valid)


# ---- per-query step bodies (traceable; statics bound via partial) --------


def _init_body(cand, count, *, bits: int, n_values: int, dedup: bool):
    """First table: normalize padding, per-row injectivity, dedup (a
    simple path repeats no vertex, so its columns must be distinct)."""
    valid = jnp.arange(cand.shape[0]) < count
    ok = jnp.ones(cand.shape[0], bool)
    for a in range(cand.shape[1]):
        for b in range(a + 1, cand.shape[1]):
            ok &= cand[:, a] != cand[:, b]
    return _settle(cand, valid & ok, bits, n_values, dedup=dedup)


def _bounds_body(table, cand, count_c, t_idx, c_idx, *, bits: int, n_values: int):
    """Group the candidate side by its shared-column key and locate every
    table row's run of equal keys (the sort-merge core).  Sentinel table
    rows (id ``n_values``) never meet sentinel candidate rows
    (``n_values + 1``), so their runs are empty by construction.

    Paths overwhelmingly share ONE vertex with the partial table, and a
    single-column key is a vertex id < n_values — so the run bounds come
    from a dense bincount + exclusive cumsum over the id space (one O(1)
    gather per probe, no binary search).  Multi-column keys take the
    packed-word sort + ``run_lookup`` search path.
    """
    cand = jnp.where((jnp.arange(cand.shape[0]) < count_c)[:, None], cand, n_values + 1)
    if c_idx.shape[0] == 1 and n_values + 2 <= 8 * cand.shape[0]:
        # dense path only while the per-vertex run table is comparable to
        # the candidate bucket itself — on huge graphs with small
        # candidate sets the O(n_vertices) bincount+cumsum would dwarf
        # the join, so those take the packed-key search below
        ckey = jnp.take(cand, c_idx[0], axis=1)
        order_c = jnp.argsort(ckey, stable=True)
        counts = jnp.zeros(n_values + 2, jnp.int32).at[ckey].add(1)
        starts = jnp.cumsum(counts) - counts
        tkey = jnp.take(table, t_idx[0], axis=1)
        lo = starts[tkey]
        hi = lo + counts[tkey]
    else:
        ck = pack_words(jnp.take(cand, c_idx, axis=1), bits)
        order_c = lex_order(ck)
        lo, hi = run_lookup(ck[order_c], pack_words(jnp.take(table, t_idx, axis=1), bits))
    return cand[order_c], lo, hi, jnp.sum(hi - lo)


def _merge_body(table, cand_s, lo, hi, n_idx, *, cap: int, bits: int, n_values: int, dedup: bool):
    """Run-length pair expansion → merged rows → injectivity → settle."""
    r, c, valid = expand_pairs(lo, hi, cap)
    old_w = table.shape[1]
    merged = jnp.concatenate([table[r], jnp.take(cand_s[c], n_idx, axis=1)], axis=1)
    if n_idx.shape[0]:
        valid &= injectivity_mask(merged[:, :old_w], merged[:, old_w:])
    return _settle(merged, valid, bits, n_values, dedup=dedup)


def _joinstep_body(
    table, cand, count_c, t_idx, c_idx, n_idx, *, cap: int, bits: int, n_values: int,
    dedup: bool,
):
    """Bounds + merge fused into ONE program: the grouped candidate side,
    run bounds, pair expansion, injectivity and settle never materialize
    between dispatches.  The column indices are operands, not constants:
    steps of different plans that differ only in which columns they key
    on share one compiled program.  ``cap`` is a guessed pair bucket — the
    returned ``total`` lets the host loop detect a too-small guess (truncated
    expansion) and re-run once with the exact power-of-two; guesses
    come from the previous execution of the same step signature, so a
    warm serving loop never retries."""
    cand_s, lo, hi, total = _bounds_body(
        table, cand, count_c, t_idx, c_idx, bits=bits, n_values=n_values
    )
    merged, valid, count = _merge_body(
        table, cand_s, lo, hi, n_idx, cap=cap, bits=bits, n_values=n_values, dedup=dedup,
    )
    return merged, valid, count, total


def _cartesian_body(table, valid_t, cand, n_c, *, n_idx, bits: int, n_values: int, dedup: bool):
    """No shared columns: every (table row, candidate row) pair (the
    paper joins connected paths, so this branch is rare and small)."""
    rt, rc = table.shape[0], cand.shape[0]
    idx = jnp.arange(rt * rc)
    r, c = idx // rc, idx % rc
    valid = valid_t[r] & (c < n_c)
    old_w = table.shape[1]
    merged = jnp.concatenate([table[r], cand[c][:, list(n_idx)]], axis=1)
    if n_idx:
        valid &= injectivity_mask(merged[:, :old_w], merged[:, old_w:])
    return _settle(merged, valid, bits, n_values, dedup=dedup)


def _compact_body(table, valid, *, n_values: int):
    """One prefix-sum scatter moves every valid row to the front — run
    ONCE per join (before refine), so refine, the host fetch and the
    match materialization all touch tight prefixes instead of the whole
    bucket.  (Per-step compaction would cost a scatter per step; the
    sentinel protocol makes it unnecessary there.)"""
    pos = jnp.cumsum(valid) - 1
    pos = jnp.where(valid, pos, table.shape[0])  # dropped rows scatter-drop
    out = jnp.full(table.shape, n_values, table.dtype)
    out = out.at[pos].set(table, mode="drop")
    return out, jnp.sum(valid)


def _refine_body(
    table, count, qlab, qedges, n_qe, qnon, n_qn, inv, ops, labels,
    *, variant: str, deg_steps: int,
):
    """Exact verification on device: label equality per column, one
    batched edge-membership search over every (row, query edge) pair,
    and (``induced``) one over every (row, query non-edge) pair.

    ``inv`` is PER QUERY (vmap axis 0): it both undoes the join's column
    order and maps canonical vertex space back to the member query's own
    vertex numbering, so the verified rows come off the device already
    in each query's match-tuple order."""
    rows = jnp.take(table, inv, axis=1)
    cap = rows.shape[0]
    ok = jnp.arange(cap) < count
    rc = jnp.clip(rows, 0, labels.shape[0] - 1)  # sentinel rows: masked by ok
    ok &= jnp.all(labels[rc] == qlab[None, :], axis=1)
    if qedges.shape[0]:
        du = jnp.take(rc, qedges[:, 0], axis=1)  # (cap, E_q)
        dv = jnp.take(rc, qedges[:, 1], axis=1)
        member = _edges_member(variant, ops, deg_steps, du, dv)
        epad = (jnp.arange(qedges.shape[0]) >= n_qe)[None, :]
        ok &= jnp.all(member | epad, axis=1)
    if qnon.shape[0]:
        du = jnp.take(rc, qnon[:, 0], axis=1)
        dv = jnp.take(rc, qnon[:, 1], axis=1)
        member = _edges_member(variant, ops, deg_steps, du, dv)
        npad = (jnp.arange(qnon.shape[0]) >= n_qn)[None, :]
        ok &= jnp.all(~member | npad, axis=1)
    return rows, ok


_STEP_BODY = {
    "init": _init_body,
    "joinstep": _joinstep_body,
    "cartesian": _cartesian_body,
    "compact": _compact_body,
    "refine": _refine_body,
}
# vmap axes per body: batched tensors lead with the query axis; shared
# graph tensors (refine's CSR + labels) and a join step's column indices
# map with in_axes=None
_STEP_AXES = {
    "init": (0, 0),
    "joinstep": (0, 0, 0, None, None, None),
    "cartesian": (0, 0, 0, 0),
    "compact": (0, 0),
    "refine": (0, 0, 0, 0, 0, 0, 0, 0, None, None),
}
_STEP_CACHE: dict = {}
# pair-bucket guesses per fused join-step signature (see _joinstep_body)
_CAP_GUESS: dict = {}
_JOIN_MESH = None  # lazily-built ("join",) mesh over the local devices


def _join_mesh():
    """Device mesh the batched join shards its query axis over — the
    same move the stacked probe makes for partitions (dist/probe.py):
    with more than one local device every join step splits its batch
    across them, so a tick's queries join in parallel while the host
    join is pinned to one thread.  Single-device setups stay on plain
    ``jit(vmap(...))``."""
    global _JOIN_MESH
    if _JOIN_MESH is None:
        devices = jax.local_devices()
        _JOIN_MESH = (
            jax.make_mesh(
                (len(devices),), ("join",), devices=devices,
                axis_types=(jax.sharding.AxisType.Auto,),
            )
            if len(devices) > 1
            else False
        )
    return _JOIN_MESH or None


def _step_fn(kind: str, **statics):
    """Jitted, vmapped step function cached per (kind, static config);
    shard_map'd over the ("join",) mesh when >1 device is present."""
    mesh = _join_mesh()
    key = (kind, mesh is not None, tuple(sorted(statics.items())))
    fn = _STEP_CACHE.get(key)
    if fn is None:
        from jax.sharding import PartitionSpec as P

        mapped = jax.vmap(
            functools.partial(_STEP_BODY[kind], **statics), in_axes=_STEP_AXES[kind]
        )
        if mesh is not None:
            specs = tuple(
                P("join") if ax == 0 else P() for ax in _STEP_AXES[kind]
            )
            mapped = jax.shard_map(
                mapped, mesh=mesh, in_specs=specs, out_specs=P("join")
            )
        fn = jax.jit(mapped)
        _STEP_CACHE[key] = fn
    return fn


def _mesh_batch(b: int) -> int:
    """Round a join batch up to a multiple of the mesh size (padded
    members carry zero counts and join to nothing)."""
    mesh = _join_mesh()
    if mesh is None:
        return b
    n = mesh.devices.size
    return ((b + n - 1) // n) * n


def _normalize_candidates(candidates: list) -> list:
    """Candidate arrays (host ndarray or device ``(rows, count)``) →
    uniform [(rows, count)] with host-known counts."""
    out = []
    for c in candidates:
        rows, cnt = c if isinstance(c, tuple) else (c, None)
        out.append((rows, int(cnt if cnt is not None else np.asarray(rows).shape[0])))
    return out


def _join_candidates_device_batch(
    plan_paths: list, cand_groups: list, n_values: int, assume_unique: bool = False
):
    """Drive the vmapped join steps for B same-plan queries (host
    control, device data).

    ``cand_groups[b]`` is the normalized [(rows, count)] list of query b,
    aligned with ``plan_paths``.  Join order is shared across the group
    (mean candidate count, shared-column preference) — any cover order
    yields the same final table set, order only shapes intermediates.
    Returns ``(tables (B, cap, C) device, counts (B,) host, cols)``.
    """
    bits = _key_bits(n_values)
    dedup = not assume_unique
    B = len(cand_groups)
    b_pad = _mesh_batch(B)
    if b_pad != B:  # mesh padding: phantom members join nothing
        empty = [
            (np.zeros((0, len(pp)), np.int32), 0) for pp in plan_paths
        ]
        cand_groups = list(cand_groups) + [empty] * (b_pad - B)
    cnt = np.asarray([[c[1] for c in grp] for grp in cand_groups], np.int64)  # (B, P)
    order = np.argsort(cnt.mean(axis=0), kind="stable")
    first = int(order[0])
    cap0 = pow2_at_least(int(cnt[:, first].max()), 16)
    stack0 = _stack_candidates(
        [grp[first][0] for grp in cand_groups], cnt[:, first], cap0,
        len(plan_paths[first]),
    )
    tables, valids, counts_dev = _step_fn("init", bits=bits, n_values=n_values, dedup=dedup)(
        stack0, jnp.asarray(cnt[:, first].astype(np.int32))
    )
    counts = np.asarray(counts_dev).astype(np.int64)
    cols = list(plan_paths[first])
    remaining = [int(i) for i in order[1:]]
    while remaining and counts.max() > 0:
        nxt = None
        for i in remaining:
            if set(plan_paths[i]) & set(cols):
                nxt = i
                break
        if nxt is None:
            nxt = remaining[0]
        remaining.remove(nxt)
        cand_cols = list(plan_paths[nxt])
        shared = [c for c in cand_cols if c in cols]
        new_cols = [c for c in cand_cols if c not in cols]
        t_idx = tuple(cols.index(c) for c in shared)
        c_idx = tuple(cand_cols.index(c) for c in shared)
        n_idx = tuple(cand_cols.index(c) for c in new_cols)
        capc = pow2_at_least(int(cnt[:, nxt].max()), 16)
        cstack = _stack_candidates(
            [grp[nxt][0] for grp in cand_groups], cnt[:, nxt], capc, len(cand_cols)
        )
        ccounts = jnp.asarray(cnt[:, nxt].astype(np.int32))
        if shared:
            guess_key = (n_values, t_idx, c_idx, n_idx, tables.shape[1:], cstack.shape[1:])
            cap = pow2_at_least(_CAP_GUESS.get(guess_key, cstack.shape[1]), 16)
            step_idx = [jnp.asarray(ix, jnp.int32) for ix in (t_idx, c_idx, n_idx)]
            for _ in range(2):  # second pass only on a cold/overflowed guess
                tables2, valids2, counts_dev, totals = _step_fn(
                    "joinstep", cap=cap, bits=bits, n_values=n_values, dedup=dedup,
                )(tables, cstack, ccounts, *step_idx)
                tmax = int(np.asarray(totals).max())
                if tmax <= cap:
                    break
                cap = pow2_at_least(tmax, 16)
            _CAP_GUESS[guess_key] = tmax
            if len(_CAP_GUESS) > 4096:
                _CAP_GUESS.pop(next(iter(_CAP_GUESS)))
            if tmax == 0:
                # no key matches anywhere in the batch: the join is empty.
                # Return the terminal state directly — falling through to
                # the post-loop compaction would re-derive counts from the
                # PRE-step valids and hand back a stale, narrower table
                cols = cols + new_cols
                counts[:] = 0
                tables = jnp.full(
                    (len(cand_groups), 1, len(cols)), n_values, jnp.int32
                )
                return tables, counts[:B], cols
            tables, valids = tables2, valids2
        else:
            tables, valids, counts_dev = _step_fn(
                "cartesian", n_idx=n_idx, bits=bits, n_values=n_values, dedup=dedup
            )(tables, valids, cstack, ccounts)
        counts = np.asarray(counts_dev).astype(np.int64)
        cols = cols + new_cols
    # one end-of-join compaction: refine/fetch work scales with the real
    # row counts from here on, not the last pair bucket
    tables, counts_dev = _step_fn("compact", n_values=n_values)(tables, valids)
    counts = np.asarray(counts_dev).astype(np.int64)
    tables = tables[:, : pow2_at_least(int(max(counts.max(), 1)), 16)]
    return tables, counts[:B], cols


def _join_candidates_device(
    plan_paths: list, candidates: list, n_values: int, assume_unique: bool = False
):
    """Single-query form (B=1 batch) — public ``join_candidates`` entry."""
    tables, counts, cols = _join_candidates_device_batch(
        plan_paths, [_normalize_candidates(candidates)], n_values, assume_unique
    )
    return tables[0], int(counts[0]), cols


# ---- device refine: jitted CSR edge membership ---------------------------

_DEV_EDGE_CACHE: dict = {}  # id(graph) -> (row_start, nbrs, labels, steps)


# adjacency rows at or below this width use the dense padded-neighbor
# table (one fused gather + compare-reduce, XLA CPU's fastest pattern);
# hub-heavy graphs above it take the CSR binary search instead, whose
# memory stays O(E)
_DENSE_ADJ_MAX_DEG = 64


def _edge_tensors_device(g: Graph):
    """Device-resident adjacency + vertex labels, cached per graph.

    Two membership layouts, picked by max degree at build:

      * dense — a (n, max_deg) −1-padded neighbor table; membership is
        ``any(adj[du] == dv)``: ONE fused gather + compare-reduce with
        no sequential steps (the shape XLA executes best);
      * csr — (row_start, sorted nbrs) + a row-local binary search of
        ``log2(max_degree)`` fori steps, for graphs whose hubs would
        make the dense table too wide.
    """
    key = id(g)
    cached = _DEV_EDGE_CACHE.get(key)
    if cached is None:
        max_deg = int(g.degrees.max()) if g.n_vertices else 0
        if max_deg <= _DENSE_ADJ_MAX_DEG:
            w = max(max_deg, 1)
            adj = np.full((g.n_vertices, w), -1, np.int32)
            row = np.repeat(np.arange(g.n_vertices), g.degrees)
            col = np.arange(g.nbrs.shape[0]) - np.repeat(
                np.cumsum(g.degrees) - g.degrees, g.degrees
            )
            adj[row, col] = g.nbrs
            variant, ops = "dense", {"adj": jnp.asarray(adj)}
        else:
            row_start = np.zeros(g.n_vertices + 1, np.int64)
            np.cumsum(g.degrees, out=row_start[1:])
            variant, ops = "csr", {
                "row_start": jnp.asarray(row_start.astype(np.int32)),
                "nbrs": jnp.asarray(g.nbrs.astype(np.int32)),
            }
        cached = (
            variant, ops, max(max_deg, 1).bit_length(),
            jnp.asarray(g.labels.astype(np.int32)),
        )
        _DEV_EDGE_CACHE[key] = cached
        weakref.finalize(g, _DEV_EDGE_CACHE.pop, key, None)
    return cached


def _edges_member(variant, ops, deg_steps, du, dv):
    """Membership of (du[i], dv[i]) in G's adjacency (see layouts above)."""
    if variant == "dense":
        return jnp.any(ops["adj"][du] == dv[..., None], axis=-1)
    row_start, nbrs = ops["row_start"], ops["nbrs"]
    if nbrs.shape[0] == 0:
        return jnp.zeros(du.shape, bool)
    E = nbrs.shape[0]
    lo = row_start[du]
    end = row_start[du + 1]

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        mv = nbrs[jnp.clip(mid, 0, E - 1)]
        adv = (mv < dv) & (lo < hi)
        return jnp.where(adv, mid + 1, lo), jnp.where(adv, hi, mid)

    lo, _ = jax.lax.fori_loop(0, deg_steps, body, (lo, end))
    return (lo < end) & (nbrs[jnp.clip(lo, 0, E - 1)] == dv)


def _query_edge_arrays(q: Graph, induced: bool, relabel: np.ndarray | None = None):
    """(labels, edges, non_edges) of a query in int32 arrays, optionally
    relabeled into canonical vertex space (``relabel[v]`` = new id)."""
    nq = q.n_vertices
    lab = np.empty(nq, np.int32)
    rl = relabel if relabel is not None else np.arange(nq)
    lab[rl] = q.labels.astype(np.int32)
    e = q.edge_array().astype(np.int64).reshape(-1, 2)
    e = rl[e].astype(np.int32)
    non = np.zeros((0, 2), np.int32)
    if induced:
        adj = q.adjacency_sets()
        pairs = [
            (rl[u], rl[v]) for u in range(nq) for v in range(u + 1, nq) if v not in adj[u]
        ]
        non = np.asarray(pairs, np.int32).reshape(-1, 2)
    return lab, e, non


def _refine_device_batch(
    g: Graph,
    qlab: np.ndarray,  # (B, nq) int32 — per-query vertex labels
    edges: list,  # per query: (E_b, 2) int32
    non_edges: list,  # per query: (N_b, 2) int32 (induced; else empty)
    tables,
    counts: np.ndarray,
    cols: list,
    colperms: np.ndarray | None = None,  # (B, nq): per-member column maps
) -> list:
    """Vmapped device refine for B same-plan queries; ONE host fetch.
    Returns per-query verified row arrays (columns = query vertex id).

    ``colperms[b, v]`` names the table column holding query b's vertex v
    (grouped joins run in canonical space, so isomorphic members need
    different maps); default = undo the join column order only."""
    B = qlab.shape[0]
    nq = qlab.shape[1]
    if not counts.max():
        return [np.zeros((0, nq), np.int32) for _ in range(B)]
    assert sorted(cols) == list(range(nq)), f"join must cover all query vertices, got {cols}"
    if colperms is None:
        colperms = np.broadcast_to(np.argsort(np.asarray(cols)), (B, nq))
    n_out = B
    b_pad = max(int(tables.shape[0]), _mesh_batch(B))
    if b_pad != int(tables.shape[0]):
        # single-query entries (B=1 public refine / scalar engine path)
        # arrive unpadded; the shard_map'd refine needs a mesh multiple —
        # phantom rows are sentinel tables with zero counts
        tables = jnp.concatenate(
            [tables, jnp.zeros((b_pad - int(tables.shape[0]),) + tables.shape[1:], tables.dtype)]
        )
    if b_pad != B:  # mesh padding (see _mesh_batch): zero-count phantoms
        qlab = np.concatenate([qlab, np.zeros((b_pad - B, nq), np.int32)])
        colperms = np.concatenate(
            [colperms, np.zeros((b_pad - B, nq), colperms.dtype)]
        )
        edges = list(edges) + [np.zeros((0, 2), np.int32)] * (b_pad - B)
        non_edges = list(non_edges) + [np.zeros((0, 2), np.int32)] * (b_pad - B)
        counts = np.concatenate([counts, np.zeros(b_pad - B, counts.dtype)])
        B = b_pad
    inv = jnp.asarray(np.ascontiguousarray(colperms).astype(np.int32))
    variant, ops, deg_steps, labels = _edge_tensors_device(g)
    e_cap = pow2_at_least(max(e.shape[0] for e in edges), 4)
    qe = np.zeros((B, e_cap, 2), np.int32)
    n_qe = np.zeros(B, np.int32)
    for b, e in enumerate(edges):
        qe[b, : e.shape[0]] = e
        n_qe[b] = e.shape[0]
    n_max = max(x.shape[0] for x in non_edges)
    n_cap = pow2_at_least(n_max, 4) if n_max else 0
    qnon = np.zeros((B, n_cap, 2), np.int32)
    n_qn = np.zeros(B, np.int32)
    for b, x in enumerate(non_edges):
        qnon[b, : x.shape[0]] = x
        n_qn[b] = x.shape[0]
    rows, ok = _step_fn("refine", variant=variant, deg_steps=deg_steps)(
        tables, jnp.asarray(counts.astype(np.int32)),
        jnp.asarray(qlab), jnp.asarray(qe), jnp.asarray(n_qe),
        jnp.asarray(qnon), jnp.asarray(n_qn),
        inv, ops, labels,
    )
    rows = np.asarray(rows)
    ok = np.asarray(ok)
    return [rows[b][ok[b]] for b in range(n_out)]


def _refine_device(
    g: Graph, q: Graph, table, count: int, cols: list, induced: bool = False
) -> list[tuple[int, ...]]:
    """Single-query device refine (B=1 batch)."""
    if count == 0:
        return []
    tables = table[None] if table.ndim == 2 else table
    lab, e, non = _query_edge_arrays(q, induced)
    out = _refine_device_batch(
        g, lab[None], [e], [non], tables, np.asarray([count], np.int64), cols
    )[0]
    # tolist() yields Python ints in one C pass — at match counts in the
    # 10⁵ range a per-element int() loop would dominate the whole refine
    return list(map(tuple, out.tolist()))


def match_from_candidates_many(
    g: Graph,
    queries: list,
    plan_paths_list: list,
    candidates_list: list,
    induced: bool = False,
    join_impl: str = "numpy",
    assume_unique: bool = False,
) -> list:
    """Batched ``match_from_candidates`` over many queries.

    With ``join_impl="device"`` queries are grouped by their WL-canonical
    signature + canonical plan shape (the same canonicalization the
    result cache keys on), and each group's multi-way join + refine runs
    in canonical vertex space as ONE vmapped device program per step —
    the serving path's join stage for a whole MatchServer tick.
    Relabeled-isomorphic queries (the repeat-heavy serving workload)
    therefore share one group even though their plan paths carry
    different vertex ids; each member's match columns map back through
    its own canonical permutation at the end.  Stragglers form singleton
    groups and cost what the per-query path costs.  The NumPy path loops
    per query (it has no batch axis).
    """
    if join_impl != "device":
        return [
            match_from_candidates(
                g, q, pp, cl, induced=induced, join_impl=join_impl,
                assume_unique=assume_unique,
            )
            for q, pp, cl in zip(queries, plan_paths_list, candidates_list)
        ]
    from .planner import canonical_form  # function-level: keeps import order

    results: list = [None] * len(queries)
    groups: dict = {}
    invs: list = []
    for qi, (q, pp) in enumerate(zip(queries, plan_paths_list)):
        perm, ckey = canonical_form(q)
        inv = np.empty(q.n_vertices, np.int64)
        inv[perm] = np.arange(q.n_vertices)
        invs.append(inv)
        canon_pp = tuple(tuple(int(inv[v]) for v in p) for p in pp)
        groups.setdefault((ckey, canon_pp), []).append(qi)
    for (ckey, canon_pp), idxs in groups.items():
        grp = [_normalize_candidates(candidates_list[qi]) for qi in idxs]
        tables, counts, cols = _join_candidates_device_batch(
            [list(p) for p in canon_pp], grp, g.n_vertices, assume_unique=assume_unique
        )
        if counts.max():
            nq = queries[idxs[0]].n_vertices
            # per-member column map: table columns are canonical ids in
            # join order; member b's vertex v lives at the column holding
            # canonical id invs[b][v] — the refine applies it on device,
            # so rows come back already in each member's own order and
            # labels/edges are passed in plain member space
            col_pos = np.argsort(np.asarray(cols))
            colperms = np.stack([col_pos[invs[qi]] for qi in idxs]).astype(np.int32)
            labs, es, nons = [], [], []
            for qi in idxs:
                lab, e, non = _query_edge_arrays(queries[qi], induced)
                labs.append(lab)
                es.append(e)
                nons.append(non)
            rows = _refine_device_batch(
                g, np.stack(labs), es, nons, tables, counts, cols, colperms=colperms
            )
        else:
            rows = [
                np.zeros((0, queries[idxs[0]].n_vertices), np.int32) for _ in idxs
            ]
        for k, qi in enumerate(idxs):
            results[qi] = list(map(tuple, rows[k].tolist()))
    return results
