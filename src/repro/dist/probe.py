"""Partition-parallel index probe: one vmapped descent over the stacked
partition tensors, shard_map'd over a ``("part",)`` device mesh.

``core/stacked.py`` lays every partition's packed forest into dense
``(S, …)`` tensors; this module runs the online filter over them:

  1. **device stage** — the level-synchronous MBR descent (Lemmas
     4.3/4.4) and, for a grouped index, the GNN-PGE group-MBR scan, as
     ONE jitted ``jax.vmap`` over the partition axis.  With more than
     one device the vmapped body is wrapped in ``jax.shard_map`` over a
     ``("part",)`` mesh, so each device scans only its (size-balanced)
     slice of the partitions — the distributed GNN-PE follow-up's
     partition-sharded traversal;
  2. **leaf stage** — the surviving (partition, query, block/group)
     cells expand to member rows across ALL partitions at once
     (vectorized on the stacked layout, no per-partition Python loop),
     ride the conservative int8 + label-hash pre-filter, and settle in
     one fused ``dominance_scan_pairs`` call (NumPy reference behind
     ``use_pallas=False``) — exactly the exact predicates of
     ``query_index_batch_multi``, so row sets are identical per
     (partition, query).

Mask math matches ``query_index_batch_multi`` — the per-partition
reference traversal the tests hold this probe to — bit for bit: both
compute float32 ``bound ± eps`` compares, and the synthesized/padded
bounds are reject sentinels that never pass (see core/stacked.py).
This is ``GnnPeEngine``'s only index probe; ``PAIR_COUNTERS`` /
per-query stats keep the reference's semantics so cost models read
identically.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from ..core import index as index_mod
from ..core.index import quantize_query
from ..core.stacked import StackedIndex, build_stacked, restack_slot, stacked_masks_ref
from ..obs import trace as obs_trace
from ..shapes import pow2_at_least

__all__ = ["StackedProbe", "survivor_cells"]


def survivor_cells(mask, cap: int):
    """Indices of ``mask``'s first ``cap`` set cells in row-major order,
    padded with 0 — what ``jnp.nonzero(mask, size=cap, fill_value=0)``
    returns.  The ``k``-th set cell is the first position whose inclusive
    count reaches ``k``: one cumsum, then a binary search of ``cap``
    lanes, ~log2(mask.size) gathers each.  ``nonzero`` instead runs a
    ``bincount`` — a scatter of one update per mask cell into ``cap``
    bins, which a TPU serialises."""
    cs = jnp.cumsum(mask.reshape(-1), dtype=jnp.int32)
    k = jnp.arange(1, cap + 1, dtype=jnp.int32)
    flat = jnp.searchsorted(cs, k, side="left", method="scan")
    flat = jnp.where(k <= cs[-1], flat, 0)
    return jnp.unravel_index(flat, mask.shape)


class StackedProbe:
    """Runs the two-level probe over a ``StackedIndex`` (see module doc).

    ``devices=None`` uses every local jax device; a single device runs
    plain ``jit(vmap(...))``, more than one shards the partition axis
    with ``shard_map`` over a ``("part",)`` mesh.

    ``leaf_pair_cap`` bounds the cross-partition leaf member-expansion:
    surviving (partition, query, block/group) cells expand to at most
    ~``cap`` (query, row) pairs per chunk, each chunk streaming through
    the pre-filter + fused exact scan before the next materializes — a
    pathological partition (huge surviving fan-out) costs extra kernel
    dispatches instead of host memory.  Results are identical for any
    cap; with the default no bench workload chunks at all.
    """

    def __init__(
        self,
        indexes: list,
        devices=None,
        stacked: StackedIndex | None = None,
        leaf_pair_cap: int = 1 << 21,
    ):
        if leaf_pair_cap < 1:
            raise ValueError(f"leaf_pair_cap must be >= 1, got {leaf_pair_cap}")
        # default to the LOCAL devices: under a multi-process
        # jax.distributed bootstrap each host probes its own shard of
        # the cluster — sharding over jax.devices() (global) would ask
        # for cross-process SPMD this probe never issues
        self.devices = list(devices) if devices is not None else list(jax.local_devices())
        self.leaf_pair_cap = int(leaf_pair_cap)
        n_dev = max(len(self.devices), 1)
        self.stacked = stacked if stacked is not None else build_stacked(indexes, n_shards=n_dev)
        # Auto axes: shardings stay out of the traced types, so the plain
        # jits downstream (cell expansion, pair stage) accept these arrays
        self.mesh = (
            jax.make_mesh(
                (n_dev,), ("part",), devices=self.devices, axis_types=(AxisType.Auto,)
            )
            if n_dev > 1
            else None
        )
        self._mask_fns: dict = {}
        # device-join support (probe_device): source indexes for the lazy
        # stacked paths tensor, jitted leaf-stage closures, and a counter
        # of host-side member expansions (0 stays 0 on the device path —
        # the bench gate's "no host round-trip" evidence)
        self._indexes = list(indexes)
        self._dev_leaf: dict | None = None
        self._leaf_fns: dict = {}
        self.host_expansions = 0
        # per-partition scanned (query, row) leaf pairs, engine model
        # order — the cluster tier's placement cost signal
        # (GnnPeEngine.partition_stats / dist/placement.py).  Cumulative
        # over the probe's lifetime, like PAIR_COUNTERS.
        self.part_leaf_pairs = np.zeros(self.stacked.n_parts, np.int64)
        self._refresh_device()

    def _refresh_device(self) -> None:
        """(Re)materialize the device-resident level/group bounds."""
        self._dev_levels = (
            tuple(self._put(x) for x in self.stacked.level_hi),
            tuple(self._put(x) for x in self.stacked.level_lo0),
            tuple(self._put(x) for x in self.stacked.level_hi0),
        )
        g = self.stacked.groups
        self._dev_groups = None
        if g is not None:
            # groups present in each leaf block: the level-1 accounting
            # (group pairs checked per surviving (query, block) cell)
            B = self.stacked.level_hi[-1].shape[1]
            gib = (g.count.reshape(self.stacked.n_slots, B, g.gpb) > 0).sum(axis=2)
            self._gib_host = gib.astype(np.int64)
            self._dev_groups = (
                self._put(g.hi), self._put(g.lo0), self._put(g.hi0),
                self._put(gib.astype(np.int32)),
            )

    def update_slot(self, part_i: int, index) -> bool:
        """Elastic re-stacking after partition ``part_i`` compacted: only
        its shard slot is rewritten (core/stacked.py ``restack_slot``) and
        the device tensors refresh — the other partitions are never
        re-stacked.  Returns ``False`` when the slot layout cannot absorb
        the new index (level count grew); the caller rebuilds the probe."""
        slot = int(self.stacked.slot_of[part_i])
        if not restack_slot(self.stacked, slot, index):
            return False
        if part_i < len(self._indexes):
            self._indexes[part_i] = index
        self._dev_leaf = None  # leaf payload moved; rebuild lazily
        self._refresh_device()
        return True

    def _put(self, x):
        if self.mesh is not None:
            return jax.device_put(x, NamedSharding(self.mesh, P("part")))
        return jnp.asarray(x)

    # ------------------------------------------------------------------
    # device stage: vmapped (and sharded) dense descent + group scan
    # ------------------------------------------------------------------
    def _mask_fn(self, use_groups: bool, eps: float):
        key = (use_groups, float(eps))
        fn = self._mask_fns.get(key)
        if fn is not None:
            return fn
        fanout = self.stacked.fanout
        gpb = self.stacked.groups.gpb if use_groups else 0

        def slot_fn(levels, group_bounds, q_cat, q0):
            """One slot's masks, plus its surviving cells and (grouped)
            group pairs checked, summed here so that the host reads the
            probe's funnel counts in the same transfer as the cell count."""
            level_hi, level_lo0, level_hi0 = levels
            alive = None
            for hi, lo0, hi0 in zip(level_hi, level_lo0, level_hi0):
                m = (
                    jnp.all(q_cat[:, None, :] <= hi[None] + eps, axis=-1)
                    & jnp.all(q0[:, None, :] <= hi0[None] + eps, axis=-1)
                    & jnp.all(q0[:, None, :] >= lo0[None] - eps, axis=-1)
                )
                if alive is not None:
                    m = m & jnp.repeat(alive, fanout, axis=1)[:, : m.shape[1]]
                alive = m
            if not use_groups:
                return alive, None, jnp.sum(alive, dtype=jnp.int32), jnp.zeros((), jnp.int32)
            g_hi, g_lo0, g_hi0, gib = group_bounds
            gkeep = (
                jnp.repeat(alive, gpb, axis=1)
                & jnp.all(q_cat[:, None, :] <= g_hi[None] + eps, axis=-1)
                & jnp.all(q0[:, None, :] <= g_hi0[None] + eps, axis=-1)
                & jnp.all(q0[:, None, :] >= g_lo0[None] - eps, axis=-1)
            )
            checked = jnp.sum(jnp.where(alive, gib[None, :], 0), dtype=jnp.int32)
            return alive, gkeep, jnp.sum(gkeep, dtype=jnp.int32), checked

        mapped = jax.vmap(slot_fn)
        if self.mesh is not None:
            mapped = jax.shard_map(
                mapped, mesh=self.mesh, in_specs=P("part"), out_specs=P("part")
            )
        fn = jax.jit(mapped)
        self._mask_fns[key] = fn
        return fn

    def _upload_queries(self, q_cat, q0):
        """(S, Q, Dcat/D0) host query tensors → device tensors with Q
        bucketed to a power of two (padded queries carry +inf and never
        survive)."""
        S, Q = q_cat.shape[:2]
        Qp = pow2_at_least(Q)
        if Qp != Q:
            q_cat = np.concatenate(
                [q_cat, np.full((S, Qp - Q, q_cat.shape[2]), np.inf, np.float32)], axis=1
            )
            q0 = np.concatenate([q0, np.zeros((S, Qp - Q, q0.shape[2]), np.float32)], axis=1)
        return self._put(q_cat), self._put(q0)

    def _masks_dev(self, q_dev, q0_dev, eps, use_groups):
        """Uploaded query tensors → DEVICE ``(alive, gkeep, cells,
        checked)`` at the bucketed query count: the masks, and per slot
        the surviving cells and the group pairs checked."""
        group_bounds = self._dev_groups if use_groups else None
        return self._mask_fn(use_groups, eps)(self._dev_levels, group_bounds, q_dev, q0_dev)

    def _device_masks(self, q_cat, q0, eps, use_groups, device_stage):
        """(S, Q, Dcat/D0) query tensors → (alive, gkeep) numpy masks."""
        if device_stage == "numpy":
            return stacked_masks_ref(self.stacked, q_cat, q0, eps, use_groups)
        Q = q_cat.shape[1]
        alive, gkeep, _, _ = self._masks_dev(*self._upload_queries(q_cat, q0), eps, use_groups)
        return np.asarray(alive[:, :Q]), (np.asarray(gkeep[:, :Q]) if use_groups else None)

    # ------------------------------------------------------------------
    # full probe: device masks → cross-partition leaf stage
    # ------------------------------------------------------------------
    def probe(
        self,
        q_emb: np.ndarray,  # (n_parts, Q, D) per-partition query embeddings
        q_emb0: np.ndarray,  # (n_parts, Q, D0)
        q_multi: np.ndarray | None = None,  # (n_gnn, n_parts, Q, D)
        q_label_hash: np.ndarray | None = None,  # (Q,) int64, shared
        eps: float = 1e-6,
        use_groups: bool = False,
        use_pallas: bool = True,
        return_stats: bool = False,
        device_stage: str = "jit",
    ):
        """Candidate rows for Q query paths against every partition.

        Returns a list (per partition, engine order) of lists (per
        query) of int64 row arrays — the same rows, in the same order,
        as ``query_index_batch_multi`` over the source indexes; with
        ``return_stats``, also the per-partition per-query stats dicts.
        """
        st = self.stacked
        if use_groups and st.groups is None and int(st.n_paths.sum()) > 0:
            raise ValueError(
                "use_groups=True needs the PackedGroupIndex sidecar — "
                "run core.grouping.attach_groups(index, group_size) first"
            )
        q_emb = np.asarray(q_emb, np.float32)
        q_emb0 = np.asarray(q_emb0, np.float32)
        n_parts, Q = q_emb.shape[:2]
        if n_parts != st.n_parts:
            raise ValueError(f"expected {st.n_parts} partitions, got {n_parts}")
        if Q == 0:
            results = [[] for _ in range(n_parts)]
            return (results, [[] for _ in range(n_parts)]) if return_stats else results
        if int(st.n_paths.sum()) == 0:
            # every partition is empty (zero length-L paths): the reference
            # returns empty row sets, so the stacked probe must too — even
            # under use_groups, where no sidecar could have been stacked
            results = [
                [np.zeros((0,), np.int64) for _ in range(Q)] for _ in range(n_parts)
            ]
            if not return_stats:
                return results
            zero = (
                {"scanned_blocks": 0, "scanned_groups": 0,
                 "surviving_groups": 0, "scanned_paths": 0}
                if use_groups
                else {"scanned_blocks": 0, "scanned_paths": 0}
            )
            return results, [[dict(zero) for _ in range(Q)] for _ in range(n_parts)]
        parts = [q_emb] + (
            [np.asarray(q_multi[i], np.float32) for i in range(st.n_gnn)] if st.n_gnn else []
        )
        cat = np.concatenate(parts, axis=2) if len(parts) > 1 else q_emb
        # scatter engine-order queries into shard-balanced slots
        S = st.n_slots
        q_cat = np.zeros((S, Q, cat.shape[2]), np.float32)
        q0 = np.zeros((S, Q, q_emb0.shape[2]), np.float32)
        q_cat[st.slot_of] = cat
        q0[st.slot_of] = q_emb0

        alive, gkeep = self._device_masks(q_cat, q0, eps, use_groups, device_stage)

        # ---- leaf stage: expand survivors across ALL partitions ----------
        # Cells (partition, query, block/group) are described by a start
        # row + member count WITHOUT materializing the rows, then expanded
        # in chunks of ≤ ~leaf_pair_cap pairs: each chunk streams through
        # the int8 pre-filter and the fused exact scan before the next
        # chunk exists, so a pathological partition cannot blow host
        # memory mid-probe.  Cell order is (pi, qi, ·)-major, so the
        # concatenated survivors stay combo-sorted for the final split.
        bs = st.block_size
        checked = member_rows = None
        if use_groups:
            g = st.groups
            B = alive.shape[2]
            groups_in_block = (g.count.reshape(S, B, g.gpb) > 0).sum(axis=2)
            checked = np.einsum("sqb,sb->sq", alive, groups_in_block)
            index_mod._GROUP_PAIRS.inc(int(checked.sum()))
            pi, qi, gi = np.nonzero(gkeep)
            index_mod._SURVIVING_GROUPS.inc(int(pi.size))
            starts = g.start[pi, gi]
            counts = g.count[pi, gi]
        else:
            pi, qi, bi = np.nonzero(alive)
            starts = bi.astype(np.int64) * bs
            counts = np.clip(st.n_paths[pi] - starts, 0, bs)
        total_pairs = int(counts.sum()) if counts.size else 0
        index_mod._LEAF_PAIRS.inc(total_pairs)
        if total_pairs:
            slot_lp = np.bincount(pi, weights=counts, minlength=S).astype(np.int64)
            self.part_leaf_pairs += slot_lp[st.slot_of]
        if return_stats and use_groups:
            member_rows = (
                np.bincount(pi * Q + qi, weights=counts, minlength=S * Q).astype(np.int64)
                if counts.size
                else np.zeros(S * Q, np.int64)
            )
        qq = quantize_query(q_cat) if st.emb_q is not None and total_pairs else None
        kept_rows: list = []
        kept_combo: list = []
        if total_pairs:
            cell_start = np.cumsum(counts) - counts
            chunk_of = cell_start // self.leaf_pair_cap  # nondecreasing
            n_chunks = int(chunk_of[-1]) + 1
            # chunks are contiguous cell ranges — slice via searchsorted
            # instead of one full boolean scan per chunk
            bounds = np.searchsorted(chunk_of, np.arange(n_chunks + 1))
        else:
            n_chunks = 0
        if n_chunks:  # (query, row) pairs materialize on the host below
            self.host_expansions += 1
        for c in range(n_chunks):
            lo, hi = int(bounds[c]), int(bounds[c + 1])
            cnt = counts[lo:hi]
            rows = index_mod._expand_segments(starts[lo:hi], cnt)
            pr = np.repeat(pi[lo:hi], cnt).astype(np.int64)
            qr = np.repeat(qi[lo:hi], cnt).astype(np.int64)
            combo = pr * Q + qr
            # conservative int8 + label-hash pre-filter (§Perf C1/C2)
            if qq is not None and rows.size:
                pre = np.all(qq[pr, qr] <= st.emb_q[pr, rows], axis=1)
                if st.label_hash is not None and q_label_hash is not None:
                    pre &= st.label_hash[pr, rows] == np.asarray(q_label_hash)[qr]
                rows, pr, qr, combo = rows[pre], pr[pre], qr[pre], combo[pre]
            # exact Lemma 4.1 + 4.2 verdicts — one fused pass per chunk
            if use_pallas:
                keep = index_mod._pairs_keep_mask(
                    q_cat[pr, qr], q0[pr, qr], st.emb_cat[pr, rows], st.emb0[pr, rows],
                    eps, use_pallas=True,
                )
            else:  # label short-circuit, like _pairs_keep_mask_numpy_lazy
                keep = np.all(np.abs(st.emb0[pr, rows] - q0[pr, qr]) <= eps, axis=1)
                sub = np.nonzero(keep)[0]
                if sub.size:
                    keep[sub] = np.all(
                        q_cat[pr[sub], qr[sub]] <= st.emb_cat[pr[sub], rows[sub]] + eps,
                        axis=1,
                    )
            kept_rows.append(rows[keep])
            kept_combo.append(combo[keep])
        rows_all = np.concatenate(kept_rows) if kept_rows else np.zeros(0, np.int64)
        combo_all = np.concatenate(kept_combo) if kept_combo else np.zeros(0, np.int64)
        splits = np.split(
            rows_all, np.cumsum(np.bincount(combo_all, minlength=S * Q))[:-1]
        )
        results = [
            [splits[int(st.slot_of[i]) * Q + qj] for qj in range(Q)]
            for i in range(n_parts)
        ]
        if not return_stats:
            return results
        scanned = alive.sum(axis=2)
        surviving = gkeep.sum(axis=2) if use_groups else None
        stats = []
        for i in range(n_parts):
            s = int(st.slot_of[i])
            if use_groups:
                stats.append(
                    [
                        {
                            "scanned_blocks": int(scanned[s, qj]),
                            "scanned_groups": int(checked[s, qj]),
                            "surviving_groups": int(surviving[s, qj]),
                            "scanned_paths": int(member_rows[s * Q + qj]),
                        }
                        for qj in range(Q)
                    ]
                )
            else:
                stats.append(
                    [
                        {
                            "scanned_blocks": int(scanned[s, qj]),
                            "scanned_paths": int(scanned[s, qj]) * bs,
                        }
                        for qj in range(Q)
                    ]
                )
        return results, stats

    # ------------------------------------------------------------------
    # device-resident candidate assembly (§device-join PR): the whole
    # leaf stage — cell expansion, pre-filter, exact pair scan, path-
    # vertex gather — runs as two jitted calls, and the per-probe
    # candidate VERTEX arrays stay on the device, ready for the jitted
    # merge join (core/matcher.py join_impl="device").  Only scalars
    # (cell/pair totals) and the per-probe row counts sync to the host.
    # ------------------------------------------------------------------
    def _leaf_tensors(self) -> dict:
        """Lazy device-resident leaf sidecar (incl. the stacked paths
        tensor, which ``StackedIndex`` itself does not carry)."""
        if self._dev_leaf is None:
            st = self.stacked
            p_max = st.emb_cat.shape[1]
            live = [ix for ix in self._indexes if ix.n_paths]
            L = live[0].paths.shape[1] if live else 2
            paths = np.zeros((st.n_slots, p_max, L), np.int32)
            for i, ix in enumerate(self._indexes):
                if ix.n_paths:
                    paths[int(st.slot_of[i]), : ix.n_paths] = ix.paths
            d = {
                "paths": jnp.asarray(paths),
                "emb_cat": jnp.asarray(st.emb_cat),
                "emb0": jnp.asarray(st.emb0),
                "n_paths": jnp.asarray(st.n_paths.astype(np.int32)),
                "emb_q": jnp.asarray(st.emb_q) if st.emb_q is not None else None,
            }
            if st.label_hash is not None:  # int64 → two int32 words (no x64)
                d["lh_hi"] = jnp.asarray((st.label_hash >> 32).astype(np.int32))
                d["lh_lo"] = jnp.asarray(
                    (st.label_hash & 0xFFFFFFFF).astype(np.uint32)
                )
            g = st.groups
            if g is not None:
                d["g_start"] = jnp.asarray(g.start.astype(np.int32))
                d["g_count"] = jnp.asarray(g.count.astype(np.int32))
            self._dev_leaf = d
        return self._dev_leaf

    def _cells_fn(self, use_groups: bool, cell_cap: int):
        """Jitted survivor-cell expansion: mask → (pi, qi, starts, counts,
        total pairs, pairs per slot)."""
        key = ("cells", use_groups, cell_cap)
        fn = self._leaf_fns.get(key)
        if fn is None:
            bs = self.stacked.block_size

            def cells(mask, n_cells, n_paths, g_start, g_count):
                pi, qi, ci = survivor_cells(mask, cell_cap)
                cvalid = jnp.arange(cell_cap) < n_cells
                if use_groups:
                    starts = g_start[pi, ci]
                    counts = g_count[pi, ci]
                else:
                    starts = ci.astype(jnp.int32) * bs
                    counts = jnp.clip(n_paths[pi] - starts, 0, bs)
                counts = jnp.where(cvalid, counts, 0).astype(jnp.int32)
                pi = pi.astype(jnp.int32)
                return (
                    pi,
                    qi.astype(jnp.int32),
                    starts.astype(jnp.int32),
                    counts,
                    jnp.sum(counts),
                    jnp.zeros((mask.shape[0],), jnp.int32).at[pi].add(counts),
                )

            fn = jax.jit(cells)
            self._leaf_fns[key] = fn
        return fn

    def _pairs_fn(self, pair_cap: int, quantized: bool, hashed: bool, has_live: bool, eps: float):
        """Jitted pair stage: expansion → pre-filter → exact scan →
        tombstone filter → vertex gather → probe-major compaction order."""
        key = ("pairs", pair_cap, quantized, hashed, has_live, float(eps))
        fn = self._leaf_fns.get(key)
        if fn is None:

            def pairs(pi, qi, starts, counts, total, q_cat, q0, qq, qh_hi, qh_lo, leaf, live):
                S, Q = q_cat.shape[:2]
                rows = jnp.repeat(starts, counts, total_repeat_length=pair_cap)
                ends = jnp.cumsum(counts)
                base = jnp.repeat(ends - counts, counts, total_repeat_length=pair_cap)
                rows = rows + (jnp.arange(pair_cap, dtype=jnp.int32) - base)
                pr = jnp.repeat(pi, counts, total_repeat_length=pair_cap)
                qr = jnp.repeat(qi, counts, total_repeat_length=pair_cap)
                keep = jnp.arange(pair_cap) < total
                if quantized:
                    keep &= jnp.all(qq[pr, qr] <= leaf["emb_q"][pr, rows], axis=1)
                    if hashed:
                        keep &= (leaf["lh_hi"][pr, rows] == qh_hi[qr]) & (
                            leaf["lh_lo"][pr, rows] == qh_lo[qr]
                        )
                # exact Lemma 4.1 + 4.2 predicates — same float32 ± eps
                # compares as the host leaf scan, so verdicts are identical
                keep &= jnp.all(jnp.abs(leaf["emb0"][pr, rows] - q0[pr, qr]) <= eps, axis=1)
                keep &= jnp.all(q_cat[pr, qr] <= leaf["emb_cat"][pr, rows] + eps, axis=1)
                if has_live:
                    keep &= live[pr, rows]
                verts = leaf["paths"][pr, rows]
                # probe-major compaction WITHOUT a sort: pairs arrive
                # slot-major with contiguous (slot, probe) groups, so the
                # output position of a kept pair is
                #   probe offset + kept pairs in earlier slots' groups
                #   + kept rank within its own group
                # — scatter-adds, cumsums and gathers only (XLA's CPU sort
                # would cost more than the whole rest of this stage)
                combo = pr * Q + qr
                kept_combo = jnp.where(keep, combo, S * Q)
                combo_counts = (
                    jnp.zeros((S * Q + 1,), jnp.int32).at[kept_combo].add(1)[: S * Q]
                )
                per_sb = combo_counts.reshape(S, Q)
                counts_b = per_sb.sum(axis=0)
                offs_b = jnp.cumsum(counts_b) - counts_b
                base_sb = offs_b[None, :] + (jnp.cumsum(per_sb, axis=0) - per_sb)
                first_idx = (
                    jnp.full((S * Q + 1,), pair_cap, jnp.int32)
                    .at[combo]
                    .min(jnp.arange(pair_cap, dtype=jnp.int32))[: S * Q]
                )
                ek = jnp.cumsum(keep.astype(jnp.int32)) - keep  # exclusive
                within = ek - ek[jnp.clip(first_idx[combo], 0, pair_cap - 1)]
                pos = base_sb.reshape(-1)[combo] + within
                pos = jnp.where(keep, pos, pair_cap)  # dropped: scatter-drop
                out = jnp.zeros((pair_cap, verts.shape[1]), jnp.int32)
                out = out.at[pos].set(verts, mode="drop")
                return out, counts_b, combo_counts

            fn = jax.jit(pairs)
            self._leaf_fns[key] = fn
        return fn

    def probe_device(
        self,
        q_emb: np.ndarray,  # (n_parts, Q, D)
        q_emb0: np.ndarray,  # (n_parts, Q, D0)
        q_multi: np.ndarray | None = None,  # (n_gnn, n_parts, Q, D)
        q_label_hash: np.ndarray | None = None,  # (Q,) int64, shared
        eps: float = 1e-6,
        use_groups: bool = False,
        use_pallas: bool = True,
        return_stats: bool = False,
        live_mask: np.ndarray | None = None,  # (S, P_max) bool; None = all live
    ):
        """Device-resident candidate assembly for Q probes.

        Returns ``(per_probe, part_counts[, stats])``:

          * ``per_probe[b]`` is ``(verts, count)`` — a DEVICE (count-
            prefixed) int32 array of candidate path VERTICES, already
            concatenated across every partition and filtered through
            ``live_mask`` — exactly the rows the host path would gather
            via ``index.paths[rows]``, never materialized on the host;
          * ``part_counts[mi, b]`` (host) — that probe's surviving row
            count per engine partition (cost models, cache scoping).

        The candidate sets equal ``probe`` + tombstone filtering per
        (partition, probe).  When the expansion would exceed
        ``leaf_pair_cap`` pairs the probe falls back to the chunked host
        path (counted in ``host_expansions``) and uploads the gathered
        vertices — identical results, bounded host memory.
        """
        st = self.stacked
        if use_groups and st.groups is None and int(st.n_paths.sum()) > 0:
            raise ValueError(
                "use_groups=True needs the PackedGroupIndex sidecar — "
                "run core.grouping.attach_groups(index, group_size) first"
            )
        q_emb = np.asarray(q_emb, np.float32)
        q_emb0 = np.asarray(q_emb0, np.float32)
        n_parts, Q = q_emb.shape[:2]
        if n_parts != st.n_parts:
            raise ValueError(f"expected {st.n_parts} partitions, got {n_parts}")
        L = self._indexes[0].paths.shape[1] if self._indexes else 2
        empty_b = (jnp.zeros((0, L), jnp.int32), 0)
        if Q == 0 or int(st.n_paths.sum()) == 0:
            per_b = [empty_b for _ in range(Q)]
            pc = np.zeros((n_parts, Q), np.int64)
            if not return_stats:
                return per_b, pc
            zero = (
                {"scanned_blocks": 0, "scanned_groups": 0,
                 "surviving_groups": 0, "scanned_paths": 0}
                if use_groups
                else {"scanned_blocks": 0, "scanned_paths": 0}
            )
            return per_b, pc, [[dict(zero) for _ in range(Q)] for _ in range(n_parts)]
        S = st.n_slots
        quantized = st.emb_q is not None
        hashed = quantized and st.label_hash is not None and q_label_hash is not None
        with obs_trace.step("probe", "prepare"):
            parts = [q_emb] + (
                [np.asarray(q_multi[i], np.float32) for i in range(st.n_gnn)]
                if st.n_gnn else []
            )
            cat = np.concatenate(parts, axis=2) if len(parts) > 1 else q_emb
            q_cat = np.zeros((S, Q, cat.shape[2]), np.float32)
            q0 = np.zeros((S, Q, q_emb0.shape[2]), np.float32)
            q_cat[st.slot_of] = cat
            q0[st.slot_of] = q_emb0
            masks_in = self._upload_queries(q_cat, q0)  # Q bucketed
            pairs_in = (jnp.asarray(q_cat), jnp.asarray(q0))  # Q as is
            qq = jnp.asarray(quantize_query(q_cat)) if quantized else jnp.zeros((1,), jnp.int8)
            if hashed:
                qh = np.asarray(q_label_hash)
                qh_hi = jnp.asarray((qh >> 32).astype(np.int32))
                qh_lo = jnp.asarray((qh & 0xFFFFFFFF).astype(np.uint32))
            else:
                qh_hi = qh_lo = jnp.zeros((1,), jnp.int32)
            has_live = live_mask is not None
            live = jnp.asarray(live_mask) if has_live else jnp.zeros((1, 1), bool)
            leaf = self._leaf_tensors()

        alive_p, gkeep_p, cells_s, checked_s = self._masks_dev(*masks_in, eps, use_groups)
        mask = (gkeep_p if use_groups else alive_p)[:, :Q]
        with obs_trace.wait("probe"):
            cells_s, checked_s = jax.device_get((cells_s, checked_s))
        n_cells = int(cells_s.sum())
        dummy = jnp.zeros((1, 1), jnp.int32)
        g_start = leaf.get("g_start", dummy)
        g_count = leaf.get("g_count", dummy)
        total = 0
        if n_cells:
            cell_cap = pow2_at_least(n_cells, 16)
            pi, qi, starts, counts, total_dev, slot_lp = self._cells_fn(use_groups, cell_cap)(
                mask, n_cells, leaf["n_paths"], g_start, g_count
            )
            with obs_trace.wait("probe"):
                total, slot_lp = jax.device_get((total_dev, slot_lp))
            total = int(total)
        if total > self.leaf_pair_cap:
            # pathological fan-out: chunked host expansion (bounded host
            # memory), then one upload of the gathered vertex rows —
            # probe() maintains the pair counters itself
            return self._probe_device_fallback(
                q_emb, q_emb0, q_multi, q_label_hash, eps, use_groups,
                use_pallas, return_stats, live_mask,
            )
        if total == 0:
            per_b = [empty_b for _ in range(Q)]
            combo_counts = np.zeros(S * Q, np.int64)
        else:
            verts_s, counts_b, combo_counts = self._pairs_fn(
                pow2_at_least(total, 16), quantized, hashed, has_live, eps
            )(
                pi, qi, starts, counts, total_dev, *pairs_in, qq, qh_hi, qh_lo, leaf, live,
            )
            with obs_trace.wait("probe"):
                counts_b, combo_counts = jax.device_get((counts_b, combo_counts))
            with obs_trace.step("probe", "slice"):
                offs = np.concatenate([[0], np.cumsum(counts_b)])
                # each query's rows are a power-of-two-long slice (the join
                # reads only ``count`` of them), so the eager slices compile
                # a handful of shapes, not one per candidate count
                lens = [pow2_at_least(int(c), 16) for c in counts_b]
                verts_p = jnp.pad(verts_s, ((0, max(lens)), (0, 0)))
                per_b = [
                    (verts_p[int(offs[b]) : int(offs[b]) + lens[b]], int(counts_b[b]))
                    for b in range(Q)
                ]
        with obs_trace.step("probe", "account"):
            index_mod._LEAF_PAIRS.inc(total)
            if use_groups:
                # level-1 accounting matches the host probe: groups checked
                # per surviving (query, block) cell, and groups surviving
                index_mod._GROUP_PAIRS.inc(int(checked_s.sum()))
                index_mod._SURVIVING_GROUPS.inc(n_cells)
            if total:
                # the same per-partition cost signal as the host path
                self.part_leaf_pairs += slot_lp.astype(np.int64)[st.slot_of]
            part_counts = np.asarray(combo_counts).reshape(S, Q)[st.slot_of.astype(np.int64)]
        if not return_stats:
            return per_b, part_counts
        stats = self._device_probe_stats(
            alive_p[:, :Q], gkeep_p[:, :Q] if use_groups else None, use_groups, Q
        )
        return per_b, part_counts, stats

    def _device_probe_stats(self, alive, gkeep, use_groups, Q):
        """Per-(partition, probe) traversal stats, ``query_index_batch_multi``
        semantics."""
        st = self.stacked
        alive_np = np.asarray(alive)
        scanned = alive_np.sum(axis=2)
        stats = []
        if use_groups:
            g = st.groups
            checked = np.einsum("sqb,sb->sq", alive_np, self._gib_host)
            gkeep_np = np.asarray(gkeep)
            surviving = gkeep_np.sum(axis=2)
            # member rows per (slot, probe): surviving groups' counts
            member = np.einsum("sqg,sg->sq", gkeep_np, g.count)
        for i in range(st.n_parts):
            s = int(st.slot_of[i])
            if use_groups:
                stats.append(
                    [
                        {
                            "scanned_blocks": int(scanned[s, qj]),
                            "scanned_groups": int(checked[s, qj]),
                            "surviving_groups": int(surviving[s, qj]),
                            "scanned_paths": int(member[s, qj]),
                        }
                        for qj in range(Q)
                    ]
                )
            else:
                stats.append(
                    [
                        {
                            "scanned_blocks": int(scanned[s, qj]),
                            "scanned_paths": int(scanned[s, qj]) * st.block_size,
                        }
                        for qj in range(Q)
                    ]
                )
        return stats

    def _probe_device_fallback(
        self, q_emb, q_emb0, q_multi, q_label_hash, eps, use_groups,
        use_pallas, return_stats, live_mask,
    ):
        """Chunked host path + one device upload (identical candidates)."""
        st = self.stacked
        out = self.probe(
            q_emb, q_emb0, q_multi, q_label_hash=q_label_hash, eps=eps,
            use_groups=use_groups, use_pallas=use_pallas, return_stats=return_stats,
        )
        results, stats = out if return_stats else (out, None)
        n_parts = st.n_parts
        Q = q_emb.shape[1]
        L = self._indexes[0].paths.shape[1] if self._indexes else 2
        lm = np.asarray(live_mask) if live_mask is not None else None
        per_b = []
        part_counts = np.zeros((n_parts, Q), np.int64)
        for b in range(Q):
            chunks = []
            for mi in range(n_parts):
                rows = results[mi][b]
                if lm is not None and rows.size:
                    rows = rows[lm[int(st.slot_of[mi]), rows]]
                part_counts[mi, b] = rows.size
                if rows.size:
                    chunks.append(self._indexes[mi].paths[rows])
            verts = (
                np.concatenate(chunks, axis=0).astype(np.int32)
                if chunks
                else np.zeros((0, L), np.int32)
            )
            per_b.append((jnp.asarray(verts), int(verts.shape[0])))
        if return_stats:
            return per_b, part_counts, stats
        return per_b, part_counts
