"""GNN-PE engine — the paper's Algorithm 1 end to end.

Offline:  partition → per-partition dominance GNNs (main + n multi-GNNs
over randomized labels) → node/label embeddings → path enumeration →
packed block indexes.

Online:   cost-model query plan → per-partition query embeddings →
index retrieval (Lemmas 4.1–4.4) → multi-way join → exact refinement.

Batched hot path (§Perf D — default, ``online_impl="batched"``):
``match_many`` drives a whole batch of queries through ONE fused pass
per stage instead of Python loops over (query × partition × path):

  1. star tensors of every query concatenate into one batch, so each
     partition's GNNs embed all queries' vertices in one call;
  2. every (query, plan-path) probe against a partition — including the
     ``plan_weight="dr"`` cost-model probes, which are memoized and
     reused by retrieval — stacks into one ``query_index_batch`` call:
     level-synchronous MBR masks evaluated as one compare-reduce per
     level, then one Pallas ``dominance_scan`` leaf scan for the batch;
  3. join + vectorized refine (see matcher.py) per query.

``online_impl="scalar"`` keeps the original per-(partition, path) loop
as the exactness cross-check and the benchmark baseline
(benchmarks/bench_online_batch.py measures one against the other).

Live serving (§delta): ``apply_updates`` absorbs online edge/vertex
insertions and deletions without an offline rebuild — affected paths
re-embed with the frozen partition GNNs into per-partition delta
buffers (core/delta.py), probes become ``main ∪ delta − tombstones``,
over-full partitions compact (and elastically re-stack) individually,
and the signature-keyed result cache (serve/cache.py, ``cache=True``)
serves repeat queries with partition-scoped invalidation.
"""
from __future__ import annotations

import dataclasses
import hashlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..graphs import Graph, Partitioning, expanded_partition, partition_graph
from ..obs import trace as obs_trace
from ..obs.metrics import REGISTRY as _OBS
from ..shapes import pad_rows, pow2_at_least
from .delta import (
    DeltaIndex,
    apply_graph_update,
    l_hop_reach,
    paths_touching,
    probe_delta_multi,
)
from .encoder import EncoderConfig, make_encoder
from .grouping import attach_groups
from . import index as index_mod
from .index import (
    PackedIndex,
    build_index,
    hash_labels,
    query_index,
    query_index_batch_multi,
)
from .matcher import match_from_candidates, match_from_candidates_many
from .paths import concat_path_embeddings, enumerate_paths
from .planner import QueryPlan, candidate_plan_paths, canonical_form, plan_query
from .stars import build_pair_dataset, build_star_tensors
from .training import TrainConfig, train_dominance

__all__ = ["GnnPeConfig", "PartitionModel", "GnnPeEngine", "QueryStats"]

# plan-cache bound: one QueryPlan per canonical query signature; FIFO
# eviction keeps a long-lived MatchServer from growing without limit
_PLAN_CACHE_MAX = 4096

# engine-level registry metrics (repro.obs): batch latency, per-stage
# seconds, result-cache lookup outcomes, and the pruning funnel — the
# process-wide cumulative complement to the per-query trace funnel
_M_QUERIES = _OBS.counter("gnnpe_engine_queries_total", "Queries matched via match_many")
_M_BATCH_S = _OBS.histogram(
    "gnnpe_engine_match_batch_seconds", "Wall seconds per match_many call"
)
_M_STAGE_S = _OBS.histogram(
    "gnnpe_engine_stage_seconds",
    "Wall seconds per fused pipeline stage",
    labels=("stage",),
)
_M_RCACHE = _OBS.counter(
    "gnnpe_result_cache_lookups_total",
    "Result-cache lookups by outcome",
    labels=("result",),
)
_M_FUNNEL = _OBS.counter(
    "gnnpe_funnel_total",
    "Cumulative pruning-funnel counts (candidates surviving each level)",
    labels=("stage",),
)


@dataclasses.dataclass(frozen=True)
class GnnPeConfig:
    path_length: int = 2  # l  (paper default 2)
    emb_dim: int = 2  # d  (paper default 2)
    n_multi: int = 2  # n  multi-GNNs (paper default 2)
    theta: int = 10  # degree threshold (paper default 10)
    n_partitions: int = 2  # m
    encoder: str = "gat"  # "gat" (paper) | "monotone" (beyond-paper)
    feat_dim: int = 8
    hidden_dim: int = 8
    heads: int = 3  # K = 3 (paper default)
    block_size: int = 128
    index_fanout: int = 16
    # GNN-PGE: "path" probes leaf rows directly; "grouped" adds the
    # path-group sidecar and the two-level probe (group-MBR scan first,
    # member scan on surviving groups) — identical match sets, fewer
    # leaf-level dominance comparisons (see core/grouping.py)
    index_kind: str = "path"
    group_size: int = 16  # max paths bundled per group ("grouped" only)
    # "fixed" groups every partition at ``group_size``; "auto" picks a
    # per-partition size from {8, 16, 32} at build time using the
    # grouping pass's fan-out stats (core/grouping.choose_group_size),
    # falling back to ``group_size`` semantics partition by partition
    group_size_mode: str = "fixed"
    plan_strategy: str = "aip"
    plan_weight: str = "deg"
    induced: bool = False
    quantize_index: bool = False  # §Perf C1/C2: int8 + label-hash leaf sidecar
    online_impl: str = "batched"  # "batched" (§Perf D) | "scalar" (baseline)
    # index traversal: "loop" walks one PackedIndex per partition in
    # Python; "stacked" probes the dense stacked-tensor index as one
    # vmapped descent, shard_map'd over the local devices' ("part",)
    # mesh (core/stacked.py + dist/probe.py) — identical match sets
    probe_impl: str = "loop"
    # candidate join + refine backend (core/matcher.py): "numpy" is the
    # host sort-merge join (the oracle); "device" drives the jitted
    # kernels/merge_join pipeline — with probe_impl="stacked" the leaf
    # member-expansion output feeds it without leaving the device.
    # Match SETS are identical (sort_matches order)
    join_impl: str = "numpy"
    # fused leaf scan backend: None = auto (Pallas kernel on TPU, the
    # bit-equal vectorized NumPy reference on CPU — interpret-mode Pallas
    # is an emulation, ~25× slower than XLA on the same work);
    # True forces the kernel (integration tests), False forces NumPy.
    use_pallas_scan: bool | None = None
    # live serving (§delta): signature-keyed result cache with partition-
    # scoped invalidation (serve/cache.py)
    cache: bool = False
    cache_capacity: int = 2048
    # compact a partition when its delta pressure (buffer rows + tombstones)
    # exceeds max(delta_compact_min, delta_compact_frac · main paths)
    delta_compact_frac: float = 0.25
    delta_compact_min: int = 512
    # cap on the stacked probe's cross-partition leaf member-expansion —
    # pathological partitions stream through the fused scan in bounded
    # chunks instead of materializing every (partition, query, row) pair
    stacked_leaf_pair_cap: int = 1 << 21
    seed: int = 0
    train: TrainConfig = dataclasses.field(default_factory=TrainConfig)


@dataclasses.dataclass
class PartitionModel:
    """Trained artifacts for one partition G_j."""

    members: np.ndarray  # vertices of G_j
    vertex_set: np.ndarray  # l-hop expanded vertex set (embedding support)
    params: dict  # main GNN params
    multi_params: list  # params of the n extra GNNs
    label_perms: np.ndarray  # (n, n_labels) randomized label maps
    node_emb: np.ndarray  # (n_vertices_G, d) — rows valid on vertex_set
    node_emb0: np.ndarray  # (n_vertices_G, d)
    node_emb_multi: np.ndarray  # (n, n_vertices_G, d)
    index: PackedIndex
    train_epochs: int = 0
    n_fallback: int = 0
    # live-update bookkeeping: partition id in the engine's Partitioning,
    # and the frozen all-ones fallback vertex ids (main + per multi-GNN) —
    # incremental re-embedding must reapply them bit-identically
    part_id: int = -1
    fallback_vids: np.ndarray = dataclasses.field(
        default_factory=lambda: np.zeros(0, np.int64)
    )
    fallback_vids_multi: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class QueryStats:
    plan: QueryPlan | None = None
    n_candidates: dict = dataclasses.field(default_factory=dict)
    total_paths: int = 0
    candidate_paths: int = 0
    pruning_power: float = 0.0
    filter_time: float = 0.0
    join_time: float = 0.0
    n_matches: int = 0
    cache_hit: bool = False


class GnnPeEngine:
    def __init__(self, cfg: GnnPeConfig):
        self.cfg = cfg
        self.graph: Graph | None = None
        self.partitioning: Partitioning | None = None
        self.models: list[PartitionModel] = []
        self.n_labels: int = 0
        self.offline_stats: dict = {}
        self._encoder = None  # built once per (config, n_labels); see encoder
        self._stacked_cache = None  # per-partition params stacked for vmap
        self._stacked_probe = None  # dist.probe.StackedProbe over the indexes
        self._plan_cache: dict = {}  # canonical query key -> canonical QueryPlan
        # live serving (§delta): per-partition tombstones + delta buffers,
        # the index epoch, and the signature-keyed result cache
        self.delta: DeltaIndex | None = None
        self.epoch: int = 0
        self._emb_fingerprint: bytes = b""
        # partitions whose compaction was deferred off the update path
        # (apply_updates(compaction="defer")) — drained by the serving
        # tier's background compactor via prepare/build/install_compaction
        self._pending_compaction: set[int] = set()
        # what the LAST apply_updates epoch changed, in probe-able form
        # (touched vertices + per-partition FreshRows) — the standing-query
        # tier consumes this via epoch_fresh()/match_incremental
        self._last_epoch_update: dict | None = None
        # cluster tier (§dist/cluster.py): per-partition probe-cost
        # accumulators behind partition_stats(), plus host-scoped subset
        # probes keyed by the owned-partition tuple a placement assigned
        self._part_leaf_pairs = np.zeros(0, np.int64)
        self._part_probe_rows = np.zeros(0, np.int64)
        self._subset_probes: dict = {}
        self._result_cache = None
        if cfg.cache:
            from ..serve.cache import ResultCache  # lazy: avoids core↔serve cycle

            self._result_cache = ResultCache(cfg.cache_capacity)

    @property
    def encoder(self):
        """The shared encoder instance (constructed once, reused by every
        offline/online embedding call — not per partition per query)."""
        if self._encoder is None:
            self._encoder = make_encoder(self._encoder_cfg())
        return self._encoder

    # ------------------------------------------------------------------
    # Offline pre-computation (Alg. 1 lines 1-5)
    # ------------------------------------------------------------------
    def build(self, g: Graph) -> "GnnPeEngine":
        cfg = self.cfg
        if cfg.index_kind not in ("path", "grouped"):
            raise ValueError(
                f"unknown index_kind {cfg.index_kind!r}; use 'path' or 'grouped'"
            )
        if cfg.probe_impl not in ("loop", "stacked"):
            raise ValueError(
                f"unknown probe_impl {cfg.probe_impl!r}; use 'loop' or 'stacked'"
            )
        if cfg.join_impl not in ("numpy", "device"):
            raise ValueError(
                f"unknown join_impl {cfg.join_impl!r}; use 'numpy' or 'device'"
            )
        if cfg.group_size_mode not in ("fixed", "auto"):
            raise ValueError(
                f"unknown group_size_mode {cfg.group_size_mode!r}; use 'fixed' or 'auto'"
            )
        t0 = time.perf_counter()
        self.graph = g
        self.n_labels = int(g.labels.max()) + 1 if g.n_vertices else 1
        self._encoder = None  # n_labels may have changed
        self._stacked_cache = None
        self.partitioning = partition_graph(g, cfg.n_partitions, seed=cfg.seed)
        rng = np.random.default_rng(cfg.seed)
        # randomized label maps shared across partitions (query side needs them)
        self.label_perms = np.stack(
            [rng.permutation(self.n_labels) for _ in range(cfg.n_multi)]
        ) if cfg.n_multi else np.zeros((0, self.n_labels), np.int64)
        train_time = 0.0
        embed_time = 0.0
        index_time = 0.0
        self.models = []
        for j in range(self.partitioning.n_parts):
            members = self.partitioning.members(j)
            vset = expanded_partition(g, self.partitioning, j, cfg.path_length)
            if vset.size == 0:
                continue
            ecfg = self._encoder_cfg()
            # ---- train main + multi GNNs over the expanded vertex set ----
            t1 = time.perf_counter()
            stars = build_star_tensors(g, vset, cfg.theta)
            pairs = build_pair_dataset(stars, rng=np.random.default_rng(cfg.seed + j))
            res = train_dominance(ecfg, stars, pairs, cfg.train)
            multi_params = []
            multi_res = []
            for i in range(cfg.n_multi):
                relab = self.label_perms[i][g.labels].astype(np.int32)
                stars_i = dataclasses.replace(
                    stars,
                    center_labels=relab[vset],
                    leaf_labels=self._relabel_leaves(stars.leaf_labels, stars.leaf_mask, i),
                )
                tcfg_i = dataclasses.replace(cfg.train, seed=cfg.train.seed + 101 + i)
                res_i = train_dominance(ecfg, stars_i, pairs, tcfg_i)
                multi_params.append(res_i.params)
                multi_res.append(res_i)
            train_time += time.perf_counter() - t1
            # ---- node embeddings (with safe fallbacks) --------------------
            t2 = time.perf_counter()
            node_emb, node_emb0 = self._node_embeddings(
                g, vset, stars, res.params, res.fallback_vertices
            )
            node_emb_multi = np.zeros((cfg.n_multi, g.n_vertices, cfg.emb_dim), np.float32)
            for i in range(cfg.n_multi):
                stars_i = dataclasses.replace(
                    stars,
                    center_labels=self.label_perms[i][g.labels][vset].astype(np.int32),
                    leaf_labels=self._relabel_leaves(stars.leaf_labels, stars.leaf_mask, i),
                )
                emb_i, _ = self._node_embeddings(
                    g, vset, stars_i, multi_params[i], multi_res[i].fallback_vertices,
                    isolated=False,
                )
                node_emb_multi[i] = emb_i
            embed_time += time.perf_counter() - t2
            # ---- paths + index -------------------------------------------
            t3 = time.perf_counter()
            paths = enumerate_paths(g, members, cfg.path_length)
            emb = concat_path_embeddings(paths, node_emb)
            emb0 = concat_path_embeddings(paths, node_emb0)
            emb_multi = (
                np.stack([concat_path_embeddings(paths, node_emb_multi[i]) for i in range(cfg.n_multi)])
                if cfg.n_multi
                else None
            )
            index = build_index(
                paths, emb, emb0, emb_multi,
                block_size=cfg.block_size, fanout=cfg.index_fanout,
                quantize=cfg.quantize_index,
                path_labels=g.labels[paths] if cfg.quantize_index else None,
            )
            if cfg.index_kind == "grouped":
                self._attach_partition_groups(index)
            index_time += time.perf_counter() - t3
            vset64 = vset.astype(np.int64)
            self.models.append(
                PartitionModel(
                    members=members,
                    vertex_set=vset,
                    params=res.params,
                    multi_params=multi_params,
                    label_perms=self.label_perms,
                    node_emb=node_emb,
                    node_emb0=node_emb0,
                    node_emb_multi=node_emb_multi,
                    index=index,
                    train_epochs=res.epochs,
                    n_fallback=len(res.fallback_vertices),
                    part_id=j,
                    fallback_vids=vset64[np.asarray(res.fallback_vertices, np.int64)]
                    if len(res.fallback_vertices)
                    else np.zeros(0, np.int64),
                    fallback_vids_multi=[
                        vset64[np.asarray(r.fallback_vertices, np.int64)]
                        if len(r.fallback_vertices)
                        else np.zeros(0, np.int64)
                        for r in multi_res
                    ],
                )
            )
        self.offline_stats = {
            "total_time": time.perf_counter() - t0,
            "train_time": train_time,
            "embed_time": embed_time,
            "index_time": index_time,
            "n_paths": int(sum(m.index.n_paths for m in self.models)),
            "index_bytes": int(sum(m.index.nbytes() for m in self.models)),
            "n_groups": int(
                sum(m.index.groups.n_groups for m in self.models if m.index.groups)
            ),
            "group_sizes": [
                int(m.index.groups.group_size) for m in self.models if m.index.groups
            ],
            "group_bytes": int(
                sum(m.index.groups.nbytes() for m in self.models if m.index.groups)
            ),
            "edge_cut": int(self.partitioning.edge_cut(g)),
        }
        self._stacked_probe = None  # indexes changed; restack lazily
        self._subset_probes.clear()
        self._part_leaf_pairs = np.zeros(len(self.models), np.int64)
        self._part_probe_rows = np.zeros(len(self.models), np.int64)
        self.delta = DeltaIndex([m.index for m in self.models]) if self.models else None
        self._pending_compaction.clear()
        self.epoch = 0
        self._last_epoch_update = None
        self._emb_fingerprint = self._content_fingerprint()
        # dr plans probed the PREVIOUS build's indexes; the fingerprint alone
        # is a coarse content digest, so drop the whole plan cache (deg plans
        # are query-only and re-cache cheaply)
        self._plan_cache.clear()
        if self._result_cache is not None:
            self._result_cache.clear()
        if cfg.probe_impl == "stacked" and self.models:
            self.stacked_probe()  # eager: pay stacking offline, report bytes
        return self

    def _attach_partition_groups(self, index) -> None:
        """Attach the group sidecar: the tuned per-partition pick under
        ``group_size_mode="auto"`` (reusing the winning trial grouping),
        else the global ``cfg.group_size``."""
        if self.cfg.group_size_mode == "auto":
            from .grouping import _best_grouping

            index.groups = _best_grouping(index)[1]
        else:
            attach_groups(index, self.cfg.group_size)

    def stacked_probe(self):
        """The dense stacked-tensor probe over every partition's index
        (built lazily, cached until the next ``build``).  Stacking
        padding overhead lands in ``offline_stats`` (``stacked_*``)."""
        if self._stacked_probe is None:
            assert self.models, "call build() first"
            from ..dist.probe import StackedProbe  # lazy: avoids core↔dist cycle

            self._stacked_probe = StackedProbe(
                [m.index for m in self.models],
                leaf_pair_cap=self.cfg.stacked_leaf_pair_cap,
            )
            self.offline_stats.update(self._stacked_probe.stacked.padding_stats())
        return self._stacked_probe

    def _subset_probe(self, parts: tuple):
        """Host-scoped stacked probe over just ``parts`` (ascending model
        indices) — the cluster tier's per-host traversal: a host stacks
        and scans only the partitions placement assigned to it, so probe
        work scales down with ownership instead of every host paying the
        full descent.  Cached per parts tuple; dropped whenever any
        partition's index object changes (compaction install, rebuild,
        generation swap)."""
        probe = self._subset_probes.get(parts)
        if probe is None:
            from ..dist.probe import StackedProbe  # lazy: avoids core↔dist cycle

            probe = StackedProbe(
                [self.models[mi].index for mi in parts],
                leaf_pair_cap=self.cfg.stacked_leaf_pair_cap,
            )
            self._subset_probes[parts] = probe
        return probe

    def _ensure_part_counters(self) -> None:
        n = len(self.models)
        if self._part_leaf_pairs.size != n:
            self._part_leaf_pairs = np.zeros(n, np.int64)
            self._part_probe_rows = np.zeros(n, np.int64)

    def partition_stats(self) -> list:
        """Stable per-partition cost/size stats for the cluster tier's
        placement model (dist/placement.py) — the supported surface over
        what were internal counters.  One dict per partition model:

          * ``part_id``     — partition id in the engine's Partitioning;
          * ``rows``        — live main-index paths;
          * ``nbytes``      — packed index bytes;
          * ``leaf_pairs``  — cumulative (query, row) leaf pairs the
            stacked probe scanned against this partition (0 until a
            stacked probe ran — placement then falls back to rows);
          * ``probe_rows``  — cumulative candidate rows this partition
            served to joins (all probe impls, main + delta);
          * ``delta_rows``/``tombstones`` — current delta pressure.
        """
        self._ensure_part_counters()
        out = []
        for mi, m in enumerate(self.models):
            dp = self.delta.parts[mi] if self.delta is not None else None
            out.append(
                {
                    "part_id": int(m.part_id),
                    "rows": int(m.index.n_paths),
                    "nbytes": int(m.index.nbytes()),
                    "leaf_pairs": int(self._part_leaf_pairs[mi]),
                    "probe_rows": int(self._part_probe_rows[mi]),
                    "delta_rows": int(dp.n_rows) if dp is not None else 0,
                    "tombstones": int(dp.n_tombstones) if dp is not None else 0,
                }
            )
        return out

    def _content_fingerprint(self) -> bytes:
        """Digest identifying the current index/embedding content — the
        "embedding fingerprint" the dr-plan cache keys on.  Seeded from
        the build, then chained through every update epoch, so a dr plan
        cached against one index state can never serve another."""
        h = hashlib.blake2b(digest_size=12)
        h.update(np.int64(self.cfg.seed).tobytes())
        h.update(np.asarray([m.index.n_paths for m in self.models], np.int64).tobytes())
        return h.digest()

    def _bump_fingerprint(self, token: bytes) -> None:
        h = hashlib.blake2b(digest_size=12)
        h.update(self._emb_fingerprint)
        h.update(token)
        self._emb_fingerprint = h.digest()

    def _encoder_cfg(self) -> EncoderConfig:
        cfg = self.cfg
        return EncoderConfig(
            n_labels=self.n_labels,
            feat_dim=cfg.feat_dim,
            hidden_dim=cfg.hidden_dim,
            heads=cfg.heads,
            out_dim=cfg.emb_dim,
            theta=cfg.theta,
            kind=cfg.encoder,
        )

    def _relabel_leaves(self, leaf_labels: np.ndarray, leaf_mask: np.ndarray, i: int) -> np.ndarray:
        out = self.label_perms[i][leaf_labels].astype(np.int32)
        return np.where(leaf_mask, out, 0)

    def _embed_star_rows(self, params, center_labels, leaf_labels, leaf_mask, isolated=True):
        """(o, o0) for a batch of stars; o0 is None unless ``isolated``.
        The batch pads to a power-of-two bucket so the jitted encoder sees
        a handful of recurring shapes instead of one per partition or
        touched-set size; star embedding is row-independent, so the
        padding changes no real row."""
        enc = self.encoder
        n = center_labels.shape[0]
        m = pow2_at_least(n)
        c = pad_rows(center_labels, m)
        o = enc.embed_stars(params, c, pad_rows(leaf_labels, m), pad_rows(leaf_mask, m))
        o = np.asarray(o).astype(np.float32)[:n]
        if not isolated:
            return o, None
        return o, np.asarray(enc.embed_isolated(params, c)).astype(np.float32)[:n]

    def _node_embeddings(self, g, vset, stars, params, fallback_vertices, isolated=True):
        """Embed every vertex of the expanded set; all-ones for overflow/fallback.
        The isolated-vertex table is None unless ``isolated``."""
        cfg = self.cfg
        o, o0 = self._embed_star_rows(
            params, stars.center_labels, stars.leaf_labels, stars.leaf_mask, isolated
        )
        # paper: high-degree → all-ones; ours: unverified vertices too
        o[stars.overflow] = 1.0
        if len(fallback_vertices):
            o[np.asarray(fallback_vertices, dtype=np.int64)] = 1.0
        node_emb = np.zeros((g.n_vertices, cfg.emb_dim), np.float32)
        node_emb[vset] = o
        if o0 is None:
            return node_emb, None
        node_emb0 = np.zeros((g.n_vertices, cfg.emb_dim), np.float32)
        node_emb0[vset] = o0
        return node_emb, node_emb0

    # ------------------------------------------------------------------
    # Live updates (§delta): incremental maintenance with frozen GNNs
    # ------------------------------------------------------------------
    def _grow_model_arrays(self, model: PartitionModel, n_vertices: int) -> None:
        """Extend the per-vertex embedding tables for appended vertices."""
        cur = model.node_emb.shape[0]
        if cur >= n_vertices:
            return
        pad = n_vertices - cur
        d = model.node_emb.shape[1]
        model.node_emb = np.concatenate([model.node_emb, np.zeros((pad, d), np.float32)])
        model.node_emb0 = np.concatenate([model.node_emb0, np.zeros((pad, d), np.float32)])
        model.node_emb_multi = np.concatenate(
            [model.node_emb_multi, np.zeros((model.node_emb_multi.shape[0], pad, d), np.float32)],
            axis=1,
        )

    def _refresh_node_embeddings(self, model: PartitionModel, vids: np.ndarray) -> None:
        """Re-embed ``vids`` with the partition's FROZEN GNNs (paper's
        incremental-maintenance rule).  Star embedding is row-independent,
        so the refreshed rows are bit-identical to what a full-batch
        rebuild over the updated graph would compute (the delta-vs-rebuild
        equivalence rests on this; see tests/test_delta_updates.py).

        The star batch pads to a power-of-two bucket (``_embed_star_rows``):
        without it, XLA recompilation dominates the whole update path."""
        g = self.graph
        cfg = self.cfg
        stars = build_star_tensors(g, vids, cfg.theta)
        o, o0 = self._embed_star_rows(
            model.params, stars.center_labels, stars.leaf_labels, stars.leaf_mask
        )
        o[stars.overflow] = 1.0
        o[np.isin(vids, model.fallback_vids)] = 1.0
        model.node_emb[vids] = o
        model.node_emb0[vids] = o0
        for i in range(cfg.n_multi):
            relab_c = self.label_perms[i][g.labels[vids]].astype(np.int32)
            relab_l = self._relabel_leaves(stars.leaf_labels, stars.leaf_mask, i)
            oi, _ = self._embed_star_rows(
                model.multi_params[i], relab_c, np.asarray(relab_l), stars.leaf_mask,
                isolated=False,
            )
            oi[stars.overflow] = 1.0
            oi[np.isin(vids, model.fallback_vids_multi[i])] = 1.0
            model.node_emb_multi[i][vids] = oi

    def _assign_new_vertices(self, new_ids: np.ndarray) -> dict:
        """Place appended vertices into modeled partitions (majority of
        already-assigned neighbors, else the smallest modeled partition)
        and extend ``self.partitioning``.  Returns part_id → new members."""
        g = self.graph
        assignment = np.concatenate(
            [self.partitioning.assignment, np.full(new_ids.size, -1, np.int32)]
        )
        sizes = np.bincount(
            self.partitioning.assignment, minlength=self.partitioning.n_parts
        ).astype(np.int64)
        modeled = np.asarray([m.part_id for m in self.models], np.int64)
        new_members: dict[int, list] = {}
        for v in new_ids:
            nbr_parts = assignment[g.neighbors(int(v))]
            nbr_parts = nbr_parts[nbr_parts >= 0]
            pick = -1
            if nbr_parts.size:
                counts = np.bincount(nbr_parts, minlength=self.partitioning.n_parts)
                best = int(np.argmax(counts[modeled]))
                if counts[modeled][best] > 0:
                    pick = int(modeled[best])
            if pick < 0:
                pick = int(modeled[int(np.argmin(sizes[modeled]))])
            assignment[v] = pick
            sizes[pick] += 1
            new_members.setdefault(pick, []).append(int(v))
        self.partitioning = Partitioning(assignment, self.partitioning.n_parts)
        return new_members

    def apply_updates(self, updates, strategy: str = "delta", compaction: str = "inline") -> dict:
        """Absorb a batch of online graph edits (one index epoch).

        ``updates`` is one ``GraphUpdate`` or a list applied atomically.
        ``strategy="delta"`` (default) runs the incremental path: touched
        vertices re-embed under the frozen partition GNNs, affected paths
        land in per-partition delta buffers, dead main rows tombstone,
        over-full partitions compact (re-sort/re-pack just themselves and,
        for ``probe_impl="stacked"``, re-stack only their shard slot).
        ``strategy="rebuild"`` applies the same graph change but then
        re-embeds/re-enumerates/re-packs EVERY partition from scratch —
        the offline baseline benchmarks/bench_updates.py measures against.
        Matches after either strategy are identical at every epoch.

        ``compaction="defer"`` skips the inline re-pack: over-threshold
        partitions are queued on ``pending_compactions()`` for a
        background compactor (prepare → build off-thread → install) so a
        ``compact_partition`` stall never extends an update tick — probes
        stay exact either way (``main ∪ delta − tombstones`` holds at any
        pressure, compaction is purely a probe-cost optimization).  Note
        match ORDER follows index layout: a deferred partition emits the
        same match set as an inline-compacted one, byte-identical order
        only once the install lands.

        Returns a summary dict (epoch, mutated/compacted partitions,
        delta/tombstone row counts).
        """
        assert self.graph is not None, "call build() first"
        if strategy not in ("delta", "rebuild"):
            raise ValueError(f"unknown update strategy {strategy!r}; use 'delta' or 'rebuild'")
        if compaction not in ("inline", "defer"):
            raise ValueError(f"unknown compaction mode {compaction!r}; use 'inline' or 'defer'")
        if not self.models:
            raise RuntimeError("apply_updates needs at least one built partition model")
        cfg = self.cfg
        ups = list(updates) if isinstance(updates, (list, tuple)) else [updates]
        g = self.graph
        n_old = g.n_vertices
        touched_parts = []
        for u in ups:
            lab = np.asarray(u.add_vertex_labels, np.int64).reshape(-1)
            if lab.size and (lab.min() < 0 or lab.max() >= self.n_labels):
                raise ValueError(
                    f"new vertex labels must lie in [0, {self.n_labels}) — "
                    "the label vocabulary is frozen at build time"
                )
            g, t = apply_graph_update(g, u)
            touched_parts.append(t)
        touched = (
            np.unique(np.concatenate(touched_parts)) if touched_parts else np.zeros(0, np.int64)
        )
        self.graph = g
        self.epoch += 1
        new_ids = np.arange(n_old, g.n_vertices, dtype=np.int64)
        new_members = self._assign_new_vertices(new_ids) if new_ids.size else {}
        for model in self.models:
            add = new_members.get(model.part_id)
            if add:
                model.members = np.sort(
                    np.concatenate([model.members.astype(np.int64), np.asarray(add, np.int64)])
                ).astype(np.int32)

        if strategy == "rebuild":
            self.rebuild_indexes()
            self._bump_fingerprint(b"rebuild" + np.int64(self.epoch).tobytes())
            if self._result_cache is not None:
                self._result_cache.clear()
            # rebuild re-packs everything: no per-row fresh bookkeeping,
            # standing queries must fall back to a full refresh
            self._last_epoch_update = {"epoch": self.epoch, "strategy": "rebuild"}
            return {
                "epoch": self.epoch,
                "strategy": "rebuild",
                "touched": int(touched.size),
                "mutated": list(range(len(self.models))),
                "compacted": [],
            }

        if self.delta is None:
            self.delta = DeltaIndex([m.index for m in self.models])
        delta = self.delta
        L = cfg.path_length
        reach = l_hop_reach(g, touched, L) if touched.size else np.zeros(0, np.int64)
        mutated: dict[int, dict] = {}
        fresh_map: dict[int, object] = {}
        compacted: list[int] = []
        n_delta_rows = 0
        n_tombstoned = 0
        for mi, model in enumerate(self.models):
            old_vset = model.vertex_set.astype(np.int64)
            touched_near = np.intersect1d(touched, old_vset, assume_unique=True)
            gained = bool(new_members.get(model.part_id))
            if touched_near.size == 0 and not gained:
                continue  # no touched vertex can reach this partition (see delta.py)
            new_vset = expanded_partition(g, self.partitioning, model.part_id, L).astype(np.int64)
            self._grow_model_arrays(model, g.n_vertices)
            need = np.union1d(
                np.setdiff1d(new_vset, old_vset, assume_unique=True),
                np.intersect1d(touched, new_vset, assume_unique=True),
            )
            if need.size:
                self._refresh_node_embeddings(model, need)
            model.vertex_set = new_vset.astype(np.int32)
            n_tomb, dropped = delta.tombstone_touched(mi, model.index, touched)
            n_tombstoned += n_tomb
            roots = np.intersect1d(model.members.astype(np.int64), reach, assume_unique=True)
            paths = enumerate_paths(g, roots.astype(np.int32), L)
            if paths.shape[0]:
                paths = paths[paths_touching(paths, touched)]
            if paths.shape[0]:
                emb = concat_path_embeddings(paths, model.node_emb)
                emb0 = concat_path_embeddings(paths, model.node_emb0)
                emb_multi = (
                    np.stack(
                        [
                            concat_path_embeddings(paths, model.node_emb_multi[i])
                            for i in range(cfg.n_multi)
                        ]
                    )
                    if cfg.n_multi
                    else np.zeros((0, paths.shape[0], emb.shape[1]), np.float32)
                )
                fresh = delta.append(mi, paths, emb, emb0, emb_multi, path_labels=g.labels[paths])
                if fresh is not None:
                    fresh_map[mi] = fresh
                n_delta_rows += paths.shape[0]
            if n_tomb or dropped or paths.shape[0]:
                mutated[mi] = {
                    "deleted": bool(n_tomb or dropped),
                    "inserted_hashes": np.unique(hash_labels(g.labels[paths]))
                    if paths.shape[0]
                    else np.zeros(0, np.int64),
                }
            if delta.needs_compaction(mi, model.index, cfg.delta_compact_frac, cfg.delta_compact_min):
                if compaction == "defer":
                    self._pending_compaction.add(mi)
                else:
                    model.index = delta.compact_partition(
                        mi, model.index, g.labels if cfg.quantize_index else None
                    )
                    self._pending_compaction.discard(mi)
                    compacted.append(mi)
        if compacted:
            # host-scoped subset probes stack index objects directly —
            # a compaction replaced some of them, so drop the lot (they
            # re-stack lazily from their owners' next probe)
            self._subset_probes.clear()
        # elastic re-stacking: only the compacted partitions' shard slots
        if self._stacked_probe is not None and compacted:
            for mi in compacted:
                if not self._stacked_probe.update_slot(mi, self.models[mi].index):
                    # the partition outgrew its slot's level layout — the
                    # (rare) full restack happens lazily on the next probe
                    self._stacked_probe = None
                    break
            if self._stacked_probe is not None:
                self.offline_stats.update(self._stacked_probe.stacked.padding_stats())
        delta.epoch = self.epoch
        if mutated:  # a no-op epoch leaves index content (and dr plans) intact
            self._bump_fingerprint(
                b"delta"
                + np.int64(self.epoch).tobytes()
                + np.asarray(sorted(mutated), np.int64).tobytes()
            )
            if self._result_cache is not None:
                self._result_cache.invalidate(mutated)
        self._last_epoch_update = {
            "epoch": self.epoch,
            "strategy": "delta",
            "touched": touched,
            "mutated": mutated,
            "fresh": fresh_map,
        }
        return {
            "epoch": self.epoch,
            "strategy": "delta",
            "touched": int(touched.size),
            "mutated": sorted(mutated),
            "compacted": compacted,
            "compaction_deferred": sorted(self._pending_compaction),
            "delta_rows_added": n_delta_rows,
            "rows_tombstoned": n_tombstoned,
            **delta.stats(),
        }

    def _rebuild_partition(self, g, partitioning, model, members=None) -> dict:
        """One partition's from-scratch re-embed + re-enumerate + re-pack
        under its FROZEN GNNs — pure: reads only frozen model state
        (params, fallback ids) and the passed graph/partitioning, and
        returns the rebuilt artifacts without installing them.
        ``rebuild_indexes`` installs inline; the blue-green generation
        path (``prepare/build/install_generation``) runs this off the
        serving path against a snapshot and installs under a version
        check."""
        cfg = self.cfg
        members = model.members if members is None else members
        vset = expanded_partition(g, partitioning, model.part_id, cfg.path_length)
        stars = build_star_tensors(g, vset, cfg.theta)
        fb = np.nonzero(np.isin(vset, model.fallback_vids))[0]
        node_emb, node_emb0 = self._node_embeddings(g, vset, stars, model.params, fb)
        node_emb_multi = np.zeros((cfg.n_multi, g.n_vertices, cfg.emb_dim), np.float32)
        for i in range(cfg.n_multi):
            stars_i = dataclasses.replace(
                stars,
                center_labels=self.label_perms[i][g.labels][vset].astype(np.int32),
                leaf_labels=self._relabel_leaves(stars.leaf_labels, stars.leaf_mask, i),
            )
            fb_i = np.nonzero(np.isin(vset, model.fallback_vids_multi[i]))[0]
            emb_i, _ = self._node_embeddings(
                g, vset, stars_i, model.multi_params[i], fb_i, isolated=False
            )
            node_emb_multi[i] = emb_i
        paths = enumerate_paths(g, members, cfg.path_length)
        emb = concat_path_embeddings(paths, node_emb)
        emb0 = concat_path_embeddings(paths, node_emb0)
        emb_multi = (
            np.stack(
                [concat_path_embeddings(paths, node_emb_multi[i]) for i in range(cfg.n_multi)]
            )
            if cfg.n_multi
            else None
        )
        index = build_index(
            paths, emb, emb0, emb_multi,
            block_size=cfg.block_size, fanout=cfg.index_fanout,
            quantize=cfg.quantize_index,
            path_labels=g.labels[paths] if cfg.quantize_index else None,
        )
        if cfg.index_kind == "grouped":
            self._attach_partition_groups(index)
        return {
            "node_emb": node_emb,
            "node_emb0": node_emb0,
            "node_emb_multi": node_emb_multi,
            "vertex_set": vset,
            "index": index,
        }

    def rebuild_indexes(self) -> "GnnPeEngine":
        """From-scratch re-embed + re-enumerate + re-pack of EVERY
        partition with the frozen per-partition GNNs.

        This is the offline baseline the delta path is measured against
        (benchmarks/bench_updates.py) and the equivalence oracle of the
        update property tests — a full ``build()`` would also re-train,
        and retrieval equality is only defined under frozen params.
        """
        assert self.graph is not None, "call build() first"
        g = self.graph
        for mi, model in enumerate(self.models):
            out = self._rebuild_partition(g, self.partitioning, model)
            model.node_emb = out["node_emb"]
            model.node_emb0 = out["node_emb0"]
            model.node_emb_multi = out["node_emb_multi"]
            model.vertex_set = out["vertex_set"]
            model.index = out["index"]
            if self.delta is not None:
                self.delta.reset_part(mi, out["index"])
        self._pending_compaction.clear()
        self.offline_stats["n_paths"] = int(sum(m.index.n_paths for m in self.models))
        self.offline_stats["index_bytes"] = int(sum(m.index.nbytes() for m in self.models))
        self._stacked_probe = None
        self._subset_probes.clear()
        if self.cfg.probe_impl == "stacked" and self.models:
            self.stacked_probe()
        return self

    def delta_stats(self) -> dict:
        """Current delta/tombstone pressure + epoch (live-serving telemetry)."""
        base = {"epoch": self.epoch}
        if self.delta is not None:
            base.update(self.delta.stats())
        if self._result_cache is not None:
            base["cache"] = self._result_cache.stats.as_dict()
        return base

    def _live_rows(self, mi: int, rows: np.ndarray) -> np.ndarray:
        """Drop tombstoned main-index rows from a probe result."""
        if self.delta is None:
            return rows
        return self.delta.live_rows(mi, rows)

    # ------------------------------------------------------------------
    # Background compaction (§serving tier): snapshot → build → install
    # ------------------------------------------------------------------
    def pending_compactions(self) -> list:
        """Partitions queued for deferred compaction, most-pressured
        first (``DeltaIndex.compaction_urgency``)."""
        if self.delta is None or not self._pending_compaction:
            return []
        cfg = self.cfg
        return sorted(
            self._pending_compaction,
            key=lambda mi: -self.delta.compaction_urgency(
                mi, self.models[mi].index, cfg.delta_compact_frac, cfg.delta_compact_min
            ),
        )

    def prepare_compaction(self, mi: int):
        """Cheap snapshot of one pending partition's (index, delta) state
        — call on the thread that owns the engine."""
        assert self.delta is not None
        return self.delta.snapshot_partition(
            mi, self.models[mi].index, self.graph.labels if self.cfg.quantize_index else None
        )

    @staticmethod
    def build_compaction(snap):
        """The expensive re-sort/re-pack.  Pure — safe on a background
        thread while the engine keeps serving probes."""
        from .delta import build_compacted_index

        return build_compacted_index(snap)

    def install_compaction(self, snap, new_index) -> bool:
        """Swap an off-thread-built compacted index in (engine thread).
        Returns False — and leaves everything untouched — if an update
        mutated the partition after the snapshot; the partition stays on
        ``pending_compactions()`` for a later retry."""
        if not (self.delta and self.delta.try_install(snap.mi, snap, new_index)):
            return False
        self.models[snap.mi].index = new_index
        self._pending_compaction.discard(snap.mi)
        self._subset_probes.clear()  # subset stacks reference the old index
        # the per-epoch liveness mask cached for the device join is keyed
        # on the epoch, which an install does NOT bump — drop it so the
        # next probe rebuilds it against the tombstone-free partition
        self._live_mask_cache = None
        if self._stacked_probe is not None:
            if self._stacked_probe.update_slot(snap.mi, new_index):
                self.offline_stats.update(self._stacked_probe.stacked.padding_stats())
            else:
                self._stacked_probe = None  # outgrew the slot; restack lazily
        return True

    # ------------------------------------------------------------------
    # Blue-green index generations (§cluster tier): snapshot → build a
    # full index generation OFF the serving path → version-checked atomic
    # install.  Content equals rebuild_indexes at the snapshot epoch (the
    # delta-vs-rebuild equivalence), so an install changes no match set
    # and — like compaction — needs no fingerprint bump.
    # ------------------------------------------------------------------
    def prepare_generation(self) -> dict:
        """Snapshot what a generation build needs (engine thread, cheap).
        ``apply_updates`` replaces — never mutates — the graph and
        partitioning objects, so holding refs is a true snapshot; members
        copy because vertex-adding updates extend them in place."""
        assert self.graph is not None, "call build() first"
        return {
            "generation": self.epoch + 1,
            "epoch": self.epoch,
            "graph": self.graph,
            "partitioning": self.partitioning,
            "members": [m.members.copy() for m in self.models],
        }

    def build_generation(self, snap: dict) -> list:
        """The expensive full rebuild against the snapshot — pure, safe
        on a background thread while the engine keeps serving probes (it
        reads only frozen params/fallbacks and the snapshot's objects)."""
        return [
            self._rebuild_partition(snap["graph"], snap["partitioning"], model, members)
            for model, members in zip(self.models, snap["members"])
        ]

    def install_generation(self, snap: dict, built: list) -> bool:
        """Atomic blue-green swap (engine thread).  Returns False — and
        leaves the serving generation untouched — when an update epoch
        landed after the snapshot: the build saw a stale graph, so the
        caller re-snapshots and rebuilds."""
        if self.epoch != snap["epoch"] or len(built) != len(self.models):
            return False
        for mi, (model, out) in enumerate(zip(self.models, built)):
            model.node_emb = out["node_emb"]
            model.node_emb0 = out["node_emb0"]
            model.node_emb_multi = out["node_emb_multi"]
            model.vertex_set = out["vertex_set"]
            model.index = out["index"]
            if self.delta is not None:
                self.delta.reset_part(mi, out["index"])
        self._pending_compaction.clear()
        self.offline_stats["n_paths"] = int(sum(m.index.n_paths for m in self.models))
        self.offline_stats["index_bytes"] = int(sum(m.index.nbytes() for m in self.models))
        # tombstones vanished without an epoch bump — the epoch-keyed
        # device-join liveness cache would serve a stale mask
        self._live_mask_cache = None
        self._stacked_probe = None
        self._subset_probes.clear()
        if self.cfg.probe_impl == "stacked" and self.models:
            self.stacked_probe()
        return True

    # ------------------------------------------------------------------
    # Per-request error scoping (§serving tier)
    # ------------------------------------------------------------------
    def match_many_isolated(
        self,
        queries: list,
        index_kind: str | None = None,
        probe_impl: str | None = None,
        join_impl: str | None = None,
    ) -> list:
        """``match_many`` with per-request fault quarantine.

        Returns ``[(ok, value), ...]`` aligned with ``queries``: ``(True,
        matches)`` on success, ``(False, exception)`` for requests whose
        presence makes the batch raise.  A raising batch re-executes by
        bisection, so one malformed/poisoned query costs O(log batch)
        extra ``match_many`` calls while every other request still
        returns exactly what a fault-free batch would have produced
        (per-query results are batch-independent by construction — see
        ``match_many``'s equivalence contract with ``impl="scalar"``).

        Exceptions marked ``transient = True`` (serve/errors.py's
        ``TransientError``) are NOT bisected: the fault is about the
        attempt, not any particular query, so re-executing halves would
        just be an unbudgeted immediate retry — the whole batch fails as
        ``(False, exc)`` and the caller's retry/backoff policy decides.
        """
        kw = dict(index_kind=index_kind, probe_impl=probe_impl, join_impl=join_impl)
        if not queries:
            return []
        try:
            return [(True, r) for r in self.match_many(queries, **kw)]
        except Exception as exc:
            if len(queries) == 1 or getattr(exc, "transient", False):
                return [(False, exc)] * len(queries)
            mid = len(queries) // 2
            return self.match_many_isolated(queries[:mid], **kw) + self.match_many_isolated(
                queries[mid:], **kw
            )

    # ------------------------------------------------------------------
    # Standing queries (§serve/standing.py)
    # ------------------------------------------------------------------
    def epoch_fresh(self) -> dict | None:
        """What the last ``apply_updates`` epoch changed, in probe-able
        form: ``{"epoch", "strategy", "touched", "mutated", "fresh"}``
        where ``fresh`` maps mutated partition → this epoch's appended
        delta rows as a ``FreshRows`` probe target.  ``strategy ==
        "rebuild"`` entries carry no row bookkeeping (standing queries
        fall back to a full refresh); ``None`` until the first update."""
        return self._last_epoch_update

    def match_incremental(self, q: Graph, state=None):
        """Standing-query evaluation step: returns ``(state, MatchDelta)``.

        First call (``state=None``) runs a full evaluation through the
        probe/join pipeline and reports every match as added; subsequent
        calls advance the cached state to the current epoch by probing
        only this epoch's fresh delta rows (see serve/standing.py for
        the algorithm and its exactness argument).
        """
        from ..serve.standing import advance_standing  # lazy: avoids core↔serve cycle

        return advance_standing(self, q, state)

    def cache_peek(self, q: Graph):
        """Result-cache lookup WITHOUT running the pipeline: the query's
        matches if its signature is cached (remapped to its own vertex
        order), else None.  The serving tier's overload fast path — a
        full queue can still answer repeat queries at cache cost."""
        if self._result_cache is None:
            return None
        from ..serve.cache import remap_matches

        perm, key = canonical_form(q)
        ent = self._result_cache.get(key, record=False)
        if ent is None:
            return None
        self._result_cache.stats.hits += 1
        return remap_matches(ent.matches, perm)

    # ------------------------------------------------------------------
    # Online matching (Alg. 1 lines 6-11, Alg. 3)
    # ------------------------------------------------------------------
    def _query_node_embeddings(self, q: Graph, model: PartitionModel):
        """Embed query stars with partition j's GNNs (query-side safety:
        overflow query vertices embed to 0⃗ so they prune nothing)."""
        cfg = self.cfg
        enc = self.encoder
        stars = build_star_tensors(q, np.arange(q.n_vertices), cfg.theta)
        o = np.asarray(
            enc.embed_stars(
                model.params,
                np.asarray(stars.center_labels),
                np.asarray(stars.leaf_labels),
                np.asarray(stars.leaf_mask),
            )
        ).astype(np.float32)
        o0 = np.asarray(
            enc.embed_isolated(model.params, np.asarray(stars.center_labels))
        ).astype(np.float32)
        o[stars.overflow] = 0.0
        o_multi = np.zeros((cfg.n_multi, q.n_vertices, cfg.emb_dim), np.float32)
        for i in range(cfg.n_multi):
            relab_c = self.label_perms[i][q.labels][np.arange(q.n_vertices)].astype(np.int32)
            relab_l = self._relabel_leaves(stars.leaf_labels, stars.leaf_mask, i)
            oi = np.asarray(
                enc.embed_stars(
                    model.multi_params[i], relab_c, np.asarray(relab_l), np.asarray(stars.leaf_mask)
                )
            ).astype(np.float32)
            oi[stars.overflow] = 0.0
            o_multi[i] = oi
        return o, o0, o_multi

    def _plan_cache_get(self, q: Graph, full_key, perm) -> QueryPlan | None:
        hit = self._plan_cache.get(full_key)
        if hit is None:
            return None
        paths = [tuple(int(perm[v]) for v in p) for p in hit.paths]
        return QueryPlan(paths=paths, cost=hit.cost, strategy=hit.strategy)

    def _plan_cache_put(self, q: Graph, full_key, perm, plan: QueryPlan) -> None:
        inv = np.empty(q.n_vertices, np.int64)
        inv[perm] = np.arange(q.n_vertices)
        while len(self._plan_cache) >= _PLAN_CACHE_MAX:
            self._plan_cache.pop(next(iter(self._plan_cache)))
        self._plan_cache[full_key] = QueryPlan(
            paths=[tuple(int(inv[v]) for v in p) for p in plan.paths],
            cost=plan.cost,
            strategy=plan.strategy,
        )

    def _dr_plan_key(self, q: Graph, group_size: int):
        """Cache key for ``weight="dr"`` plans: (canonical signature,
        embedding fingerprint) — dr weights are per-query index probe
        counts, invariant under the canonical relabeling but NOT under
        index mutation, so the fingerprint retires them at every epoch."""
        cfg = self.cfg
        perm, key = canonical_form(q)
        return perm, (
            key, cfg.path_length, cfg.plan_strategy, cfg.seed,
            "dr", self._emb_fingerprint, group_size,
        )

    def _dr_plan_peek(self, q: Graph, group_size: int) -> QueryPlan | None:
        """Cached dr plan for ``q`` at the current index epoch, or None.
        A hit lets ``match_many`` skip the candidate-path cost probes."""
        perm, full_key = self._dr_plan_key(q, group_size)
        return self._plan_cache_get(q, full_key, perm)

    def _deg_plan_cached(self, q: Graph) -> QueryPlan:
        """The ``weight="deg"`` plan under the canonical-signature cache
        — the shared implementation behind ``_plan_cached``'s deg branch
        and ``plan_cost`` (one cache, one key construction)."""
        cfg = self.cfg
        perm, key = canonical_form(q)
        full_key = (key, cfg.path_length, cfg.plan_strategy, cfg.seed)
        hit = self._plan_cache_get(q, full_key, perm)
        if hit is not None:
            return hit
        plan = plan_query(
            q, cfg.path_length,
            strategy=cfg.plan_strategy, weight="deg", seed=cfg.seed,
        )
        self._plan_cache_put(q, full_key, perm, plan)
        return plan

    def plan_cost(self, q: Graph) -> float:
        """Cheap cost estimate for scheduling: the cached ``weight="deg"``
        plan's cost (canonical-signature cache, so repeated and
        relabeled-isomorphic queries are one planner run).  Cost is
        computed on canonical ids and invariant under the relabeling, so
        the cached canonical plan's cost serves every isomorphic copy —
        MatchServer's cost-ranked tick ordering reads this.
        """
        return float(self._deg_plan_cached(q).cost)

    def _plan_cached(
        self, q: Graph, weight_fn=None, group_size: int = 1
    ) -> QueryPlan:
        """``plan_query`` with a canonical-signature cache.

        Plans under the default ``weight="deg"`` cost model depend only
        on the query's labeled structure, so repeated (even relabeled-
        isomorphic) queries in ``match_many`` batches reuse one greedy
        planner run: the plan is cached in canonical vertex ids keyed by
        ``canonical_form``'s graph bytes and mapped back through each
        query's own ordering.  ``dr`` plans weight by per-query index
        probes, so they cache under (signature, embedding fingerprint)
        — see ``_dr_plan_key`` — and re-plan only after index mutations.
        """
        cfg = self.cfg
        if weight_fn is not None and cfg.plan_weight == "dr":
            perm, full_key = self._dr_plan_key(q, group_size)
            hit = self._plan_cache_get(q, full_key, perm)
            if hit is not None:
                return hit
            plan = plan_query(
                q, cfg.path_length,
                strategy=cfg.plan_strategy, weight="dr",
                weight_fn=weight_fn, seed=cfg.seed, group_size=group_size,
            )
            self._plan_cache_put(q, full_key, perm, plan)
            return plan
        if weight_fn is not None or cfg.plan_weight != "deg":
            return plan_query(
                q, cfg.path_length,
                strategy=cfg.plan_strategy, weight=cfg.plan_weight,
                weight_fn=weight_fn, seed=cfg.seed, group_size=group_size,
            )
        return self._deg_plan_cached(q)

    def match(
        self,
        q: Graph,
        return_stats: bool = False,
        impl: str | None = None,
        probe_impl: str | None = None,
        join_impl: str | None = None,
    ):
        """Exact subgraph matching of query q (Alg. 3).

        ``impl`` overrides ``cfg.online_impl``: "batched" routes through
        ``match_many`` (the fused hot path); "scalar" runs the original
        per-(partition, path) loop (cross-check / benchmark baseline).
        ``probe_impl`` selects the index traversal ("loop" | "stacked");
        ``join_impl`` the join/refine backend ("numpy" | "device").
        """
        impl = impl or self.cfg.online_impl
        if impl == "batched":
            out = self.match_many(
                [q], return_stats=return_stats, probe_impl=probe_impl, join_impl=join_impl
            )
            if return_stats:
                matches, stats = out
                return matches[0], stats[0]
            return out[0]
        if impl != "scalar":
            raise ValueError(f"unknown online impl {impl!r}; use 'batched' or 'scalar'")
        return self._match_scalar(q, return_stats=return_stats, join_impl=join_impl)

    def _match_scalar(self, q: Graph, return_stats: bool = False, join_impl: str | None = None):
        assert self.graph is not None, "call build() first"
        cfg = self.cfg
        stats = QueryStats()
        t0 = time.perf_counter()
        # per-partition query embeddings (needed by both DR planning and retrieval)
        q_embs = [self._query_node_embeddings(q, m) for m in self.models]
        probe_memo: dict = {}
        delta = self.delta

        def _retrieve(mi: int, p: tuple):
            """→ (live main rows, delta-buffer rows) for one (partition, path)."""
            key = (mi, p)
            if key in probe_memo:
                return probe_memo[key]
            model = self.models[mi]
            pv = np.asarray(p, dtype=np.int64)
            qo, qo0, qom = q_embs[mi]
            q_emb = qo[pv].reshape(-1)
            q_emb0 = qo0[pv].reshape(-1)
            q_multi = qom[:, pv].reshape(cfg.n_multi, -1) if cfg.n_multi else None
            qh = None
            if cfg.quantize_index:
                from .index import hash_labels

                qh = int(hash_labels(q.labels[pv][None, :])[0])
            rows = query_index(model.index, q_emb, q_emb0, q_multi, q_label_hash=qh)
            rows = self._live_rows(mi, rows)
            drows = np.zeros((0,), np.int64)
            if delta is not None and delta.parts[mi].n_rows:
                out = probe_delta_multi(
                    [(
                        delta.parts[mi],
                        q_emb[None, :],
                        q_emb0[None, :],
                        q_multi[:, None, :] if q_multi is not None else None,
                        np.asarray([qh]) if qh is not None else None,
                    )],
                    use_pallas=False,
                )
                drows = out[0][0]
            probe_memo[key] = (rows, drows)
            return rows, drows

        weight_fn = None
        if cfg.plan_weight == "dr":
            # paper §5.1 alternative: w(p_q) = |DR(o(p_q))| — candidate counts
            # from an index probe (memoized; reused by the retrieval below)
            def weight_fn(p):
                return float(
                    sum(
                        sum(r.size for r in _retrieve(mi, p))
                        for mi in range(len(self.models))
                        if (self.models[mi].index.n_paths or (delta is not None and delta.parts[mi].n_rows))
                        and len(p) == self.models[mi].index.paths.shape[1]
                    )
                )

        plan = self._plan_cached(q, weight_fn=weight_fn)
        stats.plan = plan
        # candidate retrieval per partition, per query path
        candidates = [[] for _ in plan.paths]
        total_paths = 0
        for mi, model in enumerate(self.models):
            dp = delta.parts[mi] if delta is not None else None
            n_live = model.index.n_paths + (
                dp.n_rows - dp.n_tombstones if dp is not None else 0
            )
            if n_live <= 0:
                continue
            total_paths += n_live
            for pi, p in enumerate(plan.paths):
                if len(p) != model.index.paths.shape[1]:
                    continue  # length-mismatched fallback path
                rows, drows = _retrieve(mi, p)
                if rows.size:
                    candidates[pi].append(model.index.paths[rows])
                if drows.size:
                    candidates[pi].append(dp.paths[drows])
        cand_arrays = []
        cand_total = 0
        for pi, parts in enumerate(candidates):
            if parts:
                arr = np.concatenate(parts, axis=0)
            else:
                arr = np.zeros((0, len(plan.paths[pi])), np.int32)
            cand_arrays.append(arr)
            cand_total += arr.shape[0]
            stats.n_candidates[plan.paths[pi]] = int(arr.shape[0])
        stats.filter_time = time.perf_counter() - t0
        stats.total_paths = total_paths * max(len(plan.paths), 1)
        stats.candidate_paths = cand_total
        stats.pruning_power = 1.0 - cand_total / max(stats.total_paths, 1)
        # join + refine
        t1 = time.perf_counter()
        # per-path candidates are duplicate-free (partitions are root-
        # disjoint; delta rows are disjoint from live main rows), so the
        # join may skip its dedup sorts
        matches = match_from_candidates(
            self.graph, q, plan.paths, cand_arrays, induced=cfg.induced,
            join_impl=join_impl or cfg.join_impl, assume_unique=True,
        )
        stats.join_time = time.perf_counter() - t1
        stats.n_matches = len(matches)
        if return_stats:
            return matches, stats
        return matches

    # ------------------------------------------------------------------
    # Batched online matching (§Perf D): the fused multi-query hot path
    # ------------------------------------------------------------------
    def _stacked_model_params(self):
        """Per-partition GNN params stacked on a leading partition dim so
        one vmapped call embeds a star batch under EVERY partition's
        model at once (m × fewer jit dispatches on the query path)."""
        if self._stacked_cache is None:
            main = jax.tree.map(lambda *xs: jnp.stack(xs), *[m.params for m in self.models])
            multi = [
                jax.tree.map(
                    lambda *xs: jnp.stack(xs), *[m.multi_params[i] for m in self.models]
                )
                for i in range(self.cfg.n_multi)
            ]
            self._stacked_cache = (main, multi)
        return self._stacked_cache

    def _query_node_embeddings_many(self, queries: list):
        """Embed ALL queries' stars with every partition's GNNs.

        Star tensors concatenate across queries AND the partition models
        stack for ``jax.vmap``, so the whole (query batch × partition)
        embedding grid is 2 + n_multi dispatches total (instead of
        Q × m × (2+n)).  Returns ``(cat, spans)``: ``cat[mi] = (o, o0,
        o_multi)`` concatenated over queries, with query ``qi``'s rows at
        ``spans[qi]:spans[qi+1]`` — row-identical to
        ``_query_node_embeddings``.
        """
        cfg = self.cfg
        enc = self.encoder
        star_list = [build_star_tensors(q, np.arange(q.n_vertices), cfg.theta) for q in queries]
        sizes = [q.n_vertices for q in queries]
        spans = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
        centers = np.concatenate([s.center_labels for s in star_list])
        leaf_labels = np.concatenate([s.leaf_labels for s in star_list])
        leaf_mask = np.concatenate([s.leaf_mask for s in star_list])
        overflow = np.concatenate([s.overflow for s in star_list])
        if not self.models:
            return [], spans
        main, multi = self._stacked_model_params()
        o_all = np.asarray(
            jax.vmap(lambda p: enc.embed_stars(p, centers, leaf_labels, leaf_mask))(main)
        ).astype(np.float32)  # (m, n, d)
        o0_all = np.asarray(
            jax.vmap(lambda p: enc.embed_isolated(p, centers))(main)
        ).astype(np.float32)
        o_all[:, overflow] = 0.0
        om_all = np.zeros((cfg.n_multi, len(self.models), centers.shape[0], cfg.emb_dim), np.float32)
        for i in range(cfg.n_multi):
            relab_c = self.label_perms[i][centers].astype(np.int32)
            relab_l = self._relabel_leaves(leaf_labels, leaf_mask, i)
            oi = np.asarray(
                jax.vmap(lambda p: enc.embed_stars(p, relab_c, relab_l, leaf_mask))(multi[i])
            ).astype(np.float32)
            oi[:, overflow] = 0.0
            om_all[i] = oi
        cat = [
            (o_all[mi], o0_all[mi], om_all[:, mi]) for mi in range(len(self.models))
        ]
        return cat, spans

    def _stacked_live_mask(self, probe) -> np.ndarray | None:
        """(S, P_max) liveness over the stacked leaf rows (False =
        tombstoned) for the device-resident leaf stage, or None when no
        partition carries tombstones (the common case).

        Tombstones only change inside ``apply_updates`` (which bumps the
        epoch — compaction resets them in the same call), so the mask is
        cached per (epoch, stacked-probe identity) instead of being
        rebuilt and re-uploaded on every probe batch of a live-serving
        tick."""
        if self.delta is None:
            return None
        cached = getattr(self, "_live_mask_cache", None)
        if cached is not None and cached[0] == self.epoch and cached[1] is probe.stacked:
            return cached[2]
        st = probe.stacked
        mask = None
        for mi in range(min(len(self.models), len(self.delta.parts))):
            dp = self.delta.parts[mi]
            if dp.n_tomb:
                if mask is None:
                    mask = np.ones((st.n_slots, st.emb_cat.shape[1]), bool)
                s = int(st.slot_of[mi])
                n = min(dp.tombstone.size, mask.shape[1])
                mask[s, :n] = ~dp.tombstone[:n]
        if mask is not None:
            mask = jnp.asarray(mask)  # upload once per epoch, not per probe
        self._live_mask_cache = (self.epoch, probe.stacked, mask)
        return mask

    def _probe_batch(
        self,
        requests: list,
        queries: list,
        q_embs,
        memo: dict,
        use_groups: bool = False,
        stats_memo: dict | None = None,
        probe_impl: str | None = None,
        delta_memo: dict | None = None,
        dev_memo: dict | None = None,
        dev_counts: dict | None = None,
        parts: list | None = None,
    ) -> None:
        """One fused index probe for many (query, path) pairs × partitions.

        ``requests`` is a list of (qi, path) pairs; results land in
        ``memo[(mi, qi, path)]`` — the same rows separate ``query_index``
        calls would produce, from ONE ``query_index_batch_multi`` (and
        hence one Pallas leaf scan) covering every partition.  Probe
        embeddings assemble as a single gather over the concatenated
        query-star embeddings (no per-request Python loop).

        ``use_groups`` routes the probe through the GNN-PGE two-level
        scan; when ``stats_memo`` is given, per-probe traversal stats
        land in ``stats_memo[(mi, qi, path)]`` (the grouped cost model
        reads ``surviving_groups`` from there).

        ``probe_impl="stacked"`` traverses the dense stacked-tensor
        index (one vmapped/sharded descent over ALL partitions,
        dist/probe.py) instead of looping per-partition ``PackedIndex``
        objects — memo entries are identical either way.

        With live updates pending (§delta), main-index results are
        filtered through the tombstone masks and the per-partition delta
        buffers are brute-scanned into ``delta_memo[(mi, qi, path)]`` —
        together the memos hold exactly the candidate rows a rebuilt
        index would return.

        ``parts`` (cluster tier) restricts the probe to those model
        indices: a host probes only the partitions placement assigned to
        it — under ``probe_impl="stacked"`` via a host-scoped subset
        stack (``_subset_probe``), never the device-assembly path (the
        liveness mask and dev layout are full-stack-keyed).  Memo entries
        for the covered partitions are identical to an unrestricted
        probe's.
        """
        cfg = self.cfg
        cat, spans = q_embs
        reqs = list(dict.fromkeys(requests))
        # group once per path length; partitions share the probe layout
        by_len: dict = {}
        for qi, p in reqs:
            by_len.setdefault(len(p), []).append((qi, p))
        layouts = {}
        all_labels = None
        for L, sel in by_len.items():
            qi_arr = np.asarray([qi for qi, _ in sel], dtype=np.int64)
            pv_arr = np.asarray([p for _, p in sel], dtype=np.int64)  # (B, L)
            gidx = spans[qi_arr][:, None] + pv_arr  # rows in the concat stars
            qh = None
            if cfg.quantize_index:
                if all_labels is None:
                    all_labels = np.concatenate([q.labels for q in queries])
                qh = hash_labels(all_labels[gidx])
            layouts[L] = (sel, gidx, qh)
        use_pallas = (
            cfg.use_pallas_scan
            if cfg.use_pallas_scan is not None
            else jax.default_backend() == "tpu"
        )
        def query_tensors(mi, gidx, B):
            """(q_emb, q_emb0, q_multi) for partition ``mi``'s probe batch."""
            o, o0, om = cat[mi]
            return (
                o[gidx].reshape(B, -1),
                o0[gidx].reshape(B, -1),
                om[:, gidx].reshape(cfg.n_multi, B, -1) if cfg.n_multi else None,
            )

        impl = probe_impl or cfg.probe_impl
        self._ensure_part_counters()
        part_list = (
            sorted(int(mi) for mi in parts)
            if parts is not None
            else list(range(len(self.models)))
        )
        # device assembly needs the full stack (liveness mask + layout
        # are keyed on it) — a parts-scoped probe takes the host path
        use_dev = dev_memo is not None and parts is None
        if impl == "stacked" and part_list:
            # one vmapped (and device-sharded) descent over EVERY partition
            # — or, cluster-scoped, over just this host's owned ones
            L = self.models[0].index.paths.shape[1]
            if L in layouts:
                probe = (
                    self.stacked_probe()
                    if parts is None
                    else self._subset_probe(tuple(part_list))
                )
                sel, gidx, qh = layouts[L]
                B = len(sel)
                mis = part_list
                per_part = [query_tensors(mi, gidx, B) for mi in mis]
                q_emb = np.stack([t[0] for t in per_part])
                q_emb0 = np.stack([t[1] for t in per_part])
                q_multi = (
                    np.stack([t[2] for t in per_part], axis=1) if cfg.n_multi else None
                )
                lp_before = probe.part_leaf_pairs.copy()
                if use_dev:
                    # §device join: candidate vertices assemble on device,
                    # tombstones filter via the liveness mask — no host-side
                    # member expansion, no per-row result transfer
                    out = probe.probe_device(
                        q_emb, q_emb0, q_multi, q_label_hash=qh,
                        use_groups=use_groups, use_pallas=use_pallas,
                        return_stats=stats_memo is not None,
                        live_mask=self._stacked_live_mask(probe),
                    )
                    if stats_memo is not None:
                        per_b, part_counts, stats = out
                    else:
                        per_b, part_counts = out
                    for b, (qi, p) in enumerate(sel):
                        dev_memo[(qi, p)] = per_b[b]
                        for mi in mis:
                            dev_counts[(mi, qi, p)] = int(part_counts[mi, b])
                            if stats_memo is not None:
                                stats_memo[(mi, qi, p)] = stats[mi][b]
                    self._part_probe_rows += part_counts.sum(axis=1)
                else:
                    out = probe.probe(
                        q_emb, q_emb0, q_multi, q_label_hash=qh,
                        use_groups=use_groups, use_pallas=use_pallas,
                        return_stats=stats_memo is not None,
                    )
                    results, stats = out if stats_memo is not None else (out, None)
                    for li, mi in enumerate(mis):
                        for b, (qi, p) in enumerate(sel):
                            rows = self._live_rows(mi, results[li][b])
                            memo[(mi, qi, p)] = rows
                            self._part_probe_rows[mi] += rows.size
                            if stats_memo is not None:
                                stats_memo[(mi, qi, p)] = stats[li][b]
                self._part_leaf_pairs[np.asarray(mis, np.int64)] += (
                    probe.part_leaf_pairs - lp_before
                )
        else:
            items = []
            sels = []
            for mi in part_list:
                model = self.models[mi]
                if model.index.n_paths == 0:
                    continue
                L = model.index.paths.shape[1]
                if L not in layouts:
                    continue
                sel, gidx, qh = layouts[L]
                q_emb, q_emb0, q_multi = query_tensors(mi, gidx, len(sel))
                items.append((model.index, q_emb, q_emb0, q_multi, qh))
                sels.append((mi, sel))
            if items:
                # one fused traversal + ONE fused leaf scan for every partition
                out = query_index_batch_multi(
                    items,
                    use_pallas=use_pallas,
                    use_groups=use_groups,
                    return_stats=stats_memo is not None,
                )
                results, stats = out if stats_memo is not None else (out, None)
                for ii, ((mi, sel), rows_list) in enumerate(zip(sels, results)):
                    for b, (qi, p) in enumerate(sel):
                        rows = self._live_rows(mi, rows_list[b])
                        memo[(mi, qi, p)] = rows
                        self._part_probe_rows[mi] += rows.size
                        if stats_memo is not None:
                            stats_memo[(mi, qi, p)] = stats[ii][b]
        # ---- delta buffers: brute (query, row) pairs, one fused scan ----
        if delta_memo is None or self.delta is None or not self.delta.any_rows():
            return
        if not self.models:
            return
        L = self.models[0].index.paths.shape[1]
        lay = layouts.get(L)
        if lay is None:
            return
        sel, gidx, qh = lay
        d_items = []
        d_mis = []
        for mi in part_list:
            dp = self.delta.parts[mi]
            if dp.n_rows == 0:
                continue
            q_emb, q_emb0, q_multi = query_tensors(mi, gidx, len(sel))
            d_items.append((dp, q_emb, q_emb0, q_multi, qh))
            d_mis.append(mi)
        if not d_items:
            return
        d_results = probe_delta_multi(d_items, use_pallas=use_pallas)
        for mi, rows_list in zip(d_mis, d_results):
            for b, (qi, p) in enumerate(sel):
                delta_memo[(mi, qi, p)] = rows_list[b]
                self._part_probe_rows[mi] += rows_list[b].size

    def probe_candidates(
        self,
        queries: list,
        requests: list,
        parts: list | None = None,
        index_kind: str | None = None,
        probe_impl: str | None = None,
        return_stats: bool = False,
    ):
        """Cluster scatter primitive (dist/cluster.py): probe ``requests``
        — (qi, path) pairs over ``queries`` — against the partitions in
        ``parts`` (default all) and return the candidate VERTEX arrays

            {(mi, qi, path): (main_verts, delta_verts)}

        with one entry per covered partition that produced rows.  Main
        rows are live (tombstone-filtered) in index order, delta rows in
        delta-buffer order — exactly the arrays ``_match_many_core``
        concatenates, so a coordinator assembling gathered responses in
        ascending ``mi`` (main then delta per partition) reproduces the
        single-process candidate tables byte for byte.  With
        ``return_stats`` also returns ``{(mi, qi, path): stats}`` (the
        grouped cost model's ``surviving_groups`` ride-along).
        """
        assert self.graph is not None, "call build() first"
        kind = index_kind or self.cfg.index_kind
        q_embs = self._query_node_embeddings_many(queries)
        memo: dict = {}
        delta_memo: dict = {}
        stats_memo: dict | None = {} if return_stats else None
        self._probe_batch(
            list(requests), queries, q_embs, memo,
            use_groups=kind == "grouped", stats_memo=stats_memo,
            probe_impl=probe_impl, delta_memo=delta_memo, parts=parts,
        )
        out: dict = {}
        empty: dict = {}
        for (mi, qi, p), rows in memo.items():
            L = len(p)
            ev = empty.setdefault(L, np.zeros((0, L), np.int32))
            main = self.models[mi].index.paths[rows] if rows.size else ev
            out[(mi, qi, p)] = (main, ev)
        for (mi, qi, p), drows in delta_memo.items():
            L = len(p)
            ev = empty.setdefault(L, np.zeros((0, L), np.int32))
            dverts = self.delta.parts[mi].paths[drows] if drows.size else ev
            main = out[(mi, qi, p)][0] if (mi, qi, p) in out else ev
            out[(mi, qi, p)] = (main, dverts)
        if return_stats:
            return out, stats_memo
        return out

    def match_many(
        self,
        queries: list,
        return_stats: bool = False,
        index_kind: str | None = None,
        probe_impl: str | None = None,
        join_impl: str | None = None,
    ):
        """Exact subgraph matching for a batch of queries (fused Alg. 3).

        Per-query results are identical to ``match(q, impl="scalar")``;
        the filter stage runs as one fused pass per partition for the
        whole batch (shared star embedding, batched traversal, one
        Pallas leaf scan).  ``plan_weight="dr"`` cost-model probes join
        the same batch and are reused by retrieval.

        ``index_kind`` overrides ``cfg.index_kind`` for the probe layer:
        a "grouped" engine keeps its per-path arrays, so both probe
        kinds stay available for cross-checks and benchmarks.
        ``probe_impl`` likewise overrides ``cfg.probe_impl`` ("loop" |
        "stacked") — match sets are byte-identical between the two.

        With ``cfg.cache`` on, queries whose WL-canonical signature is
        cached (and not invalidated by updates) skip the pipeline: the
        cached canonical matches map back through the query's own
        ordering (serve/cache.py) — exact for relabeled-isomorphic
        repeats too.
        """
        assert self.graph is not None, "call build() first"
        cfg = self.cfg
        kind = index_kind or cfg.index_kind
        if kind not in ("path", "grouped"):
            raise ValueError(f"unknown index_kind {kind!r}; use 'path' or 'grouped'")
        impl = probe_impl or cfg.probe_impl
        if impl not in ("loop", "stacked"):
            raise ValueError(f"unknown probe_impl {impl!r}; use 'loop' or 'stacked'")
        jimpl = join_impl or cfg.join_impl
        if jimpl not in ("numpy", "device"):
            raise ValueError(f"unknown join_impl {jimpl!r}; use 'numpy' or 'device'")
        nq = len(queries)
        if nq == 0:
            return ([], []) if return_stats else []
        t_start = time.perf_counter()
        cache = self._result_cache
        if cache is None:
            results, stats, _ = self._match_many_core(queries, kind, impl, jimpl)
            _M_QUERIES.inc(nq)
            _M_BATCH_S.observe(time.perf_counter() - t_start)
            return (results, stats) if return_stats else results
        from ..serve.cache import canonical_matches, remap_matches

        canon = [canonical_form(q) for q in queries]
        results: list = [None] * nq
        stats: list = [None] * nq
        miss: list[int] = []
        with obs_trace.span("cache_lookup") as lk_span:
            for qi, (perm, key) in enumerate(canon):
                ent = cache.get(key)
                if ent is not None:
                    results[qi] = remap_matches(ent.matches, perm)
                    st = QueryStats()
                    st.cache_hit = True
                    st.n_matches = len(results[qi])
                    if ent.plan is not None:  # canonical ids → this query's ids
                        st.plan = QueryPlan(
                            paths=[tuple(int(perm[v]) for v in p) for p in ent.plan.paths],
                            cost=ent.plan.cost,
                            strategy=ent.plan.strategy,
                        )
                    stats[qi] = st
                else:
                    miss.append(qi)
            if lk_span is not None:
                lk_span.attrs["hits"] = nq - len(miss)
                lk_span.attrs["misses"] = len(miss)
        if nq - len(miss):
            _M_RCACHE.labels(result="hit").inc(nq - len(miss))
        if miss:
            _M_RCACHE.labels(result="miss").inc(len(miss))
            sub_results, sub_stats, contributing = self._match_many_core(
                [queries[qi] for qi in miss], kind, impl, jimpl
            )
            with obs_trace.span("cache_store", n_entries=len(miss)):
                for k, qi in enumerate(miss):
                    results[qi] = sub_results[k]
                    stats[qi] = sub_stats[k]
                    q = queries[qi]
                    perm, key = canon[qi]
                    plan = sub_stats[k].plan
                    plan_hashes = {
                        int(hash_labels(q.labels[np.asarray(p, np.int64)][None, :])[0])
                        for p in plan.paths
                    }
                    inv = np.empty(q.n_vertices, np.int64)
                    inv[perm] = np.arange(q.n_vertices)
                    cache.put(
                        key,
                        canonical_matches(sub_results[k], perm, q.n_vertices),
                        contributing[k],
                        plan_hashes,
                        self.epoch,
                        plan=QueryPlan(
                            paths=[tuple(int(inv[v]) for v in p) for p in plan.paths],
                            cost=plan.cost,
                            strategy=plan.strategy,
                        ),
                    )
        _M_QUERIES.inc(nq)
        _M_BATCH_S.observe(time.perf_counter() - t_start)
        return (results, stats) if return_stats else results

    def _match_many_core(self, queries: list, kind: str, impl: str, join_impl: str = "numpy"):
        """The fused batch pipeline (no result cache).  Returns
        ``(results, stats, contributing)`` where ``contributing[qi]`` is
        the set of partition (model) indices that produced candidate
        rows — what the result cache scopes its invalidation on.

        With ``join_impl="device"`` and the stacked probe, the probe
        hands back device-resident candidate vertex arrays (``dev_memo``)
        plus per-partition counts (``dev_counts``) — the join consumes
        them without a host round-trip; delta-buffer rows (small by
        construction) upload alongside.
        """
        cfg = self.cfg
        use_groups = kind == "grouped"
        nq = len(queries)
        stats = [QueryStats() for _ in range(nq)]
        trace = obs_trace.current_trace()
        pairs_before = (index_mod._GROUP_PAIRS.value, index_mod._LEAF_PAIRS.value)
        t0 = time.perf_counter()
        with obs_trace.span("embed", n_queries=nq):
            q_embs = self._query_node_embeddings_many(queries)
        t_embed = time.perf_counter()
        _M_STAGE_S.labels(stage="embed").observe(t_embed - t0)
        memo: dict = {}
        delta_memo: dict = {}
        delta = self.delta
        n_models = len(self.models)
        device_assembly = join_impl == "device" and impl == "stacked" and n_models > 0
        dev_memo: dict | None = {} if device_assembly else None
        dev_counts: dict = {}
        # ---- plans (dr probes ride the same batched pipeline) -----------
        plan_span_cm = obs_trace.span("plan", n_queries=nq)
        plan_span = plan_span_cm.__enter__()
        weight_fns: list = [None] * nq
        cached_plans: list = [None] * nq
        plan_group_size = 1
        stats_memo: dict | None = None
        if cfg.plan_weight == "dr":
            if use_groups:
                plan_group_size = cfg.group_size
            cached_plans = [self._dr_plan_peek(q, plan_group_size) for q in queries]
            probe_reqs = [
                (qi, p)
                for qi, q in enumerate(queries)
                if cached_plans[qi] is None
                for p in candidate_plan_paths(q, cfg.path_length)
            ]
            stats_memo = {} if use_groups else None
            if probe_reqs:
                self._probe_batch(
                    probe_reqs, queries, q_embs, memo,
                    use_groups=use_groups, stats_memo=stats_memo, probe_impl=impl,
                    delta_memo=delta_memo, dev_memo=dev_memo, dev_counts=dev_counts,
                )

            def _delta_rows(mi, qi, p):
                rows = delta_memo.get((mi, qi, p))
                return rows.size if rows is not None else 0

            if use_groups:
                # grouped cost model: weights are group fan-outs
                # (surviving groups — the probe's unit of leaf work)
                # instead of the per-path |DR(o(p_q))| counts the
                # two-level probe avoids materializing; plan_query's
                # group_size scale only converts the reported cost to
                # leaf-row units (selection is scale-invariant).  Delta
                # buffer rows count as ceil(rows / group_size) groups of
                # brute-pair work.
                gsz = max(cfg.group_size, 1)

                def make_weight_fn(qi):
                    def weight_fn(p):
                        w = sum(
                            stats_memo[(mi, qi, p)]["surviving_groups"]
                            for mi in range(n_models)
                            if (mi, qi, p) in stats_memo
                        )
                        w += sum(
                            -(-_delta_rows(mi, qi, p) // gsz) for mi in range(n_models)
                        )
                        return float(w)

                    return weight_fn

            else:

                def make_weight_fn(qi):
                    def weight_fn(p):
                        main = (
                            sum(
                                dev_counts.get((mi, qi, p), 0)
                                for mi in range(n_models)
                            )
                            if device_assembly
                            else sum(
                                memo[(mi, qi, p)].size
                                for mi in range(n_models)
                                if (mi, qi, p) in memo
                            )
                        )
                        return float(
                            main + sum(_delta_rows(mi, qi, p) for mi in range(n_models))
                        )

                    return weight_fn

            weight_fns = [
                make_weight_fn(qi) if cached_plans[qi] is None else None
                for qi in range(nq)
            ]
        plans = [
            cached_plans[qi]
            if cached_plans[qi] is not None
            else self._plan_cached(q, weight_fn=weight_fns[qi], group_size=plan_group_size)
            for qi, q in enumerate(queries)
        ]
        if plan_span is not None:
            plan_span.attrs["plan_cache_hits"] = sum(
                1 for p in cached_plans if p is not None
            )
        plan_span_cm.__exit__(None, None, None)
        t_plan = time.perf_counter()
        _M_STAGE_S.labels(stage="plan").observe(t_plan - t_embed)
        # ---- retrieval: one fused probe per partition for all plans -----
        todo = [
            (qi, p)
            for qi, plan in enumerate(plans)
            for p in plan.paths
            if not (
                (dev_memo is not None and (qi, p) in dev_memo)
                or any(
                    (mi, qi, p) in memo or (mi, qi, p) in delta_memo
                    for mi in range(n_models)
                )
            )
        ]
        # capture grouped traversal stats for the trace funnel (the
        # surviving-groups rung) — only when someone is actually tracing
        probe_stats: dict | None = (
            {} if (trace is not None and use_groups) else None
        )
        with obs_trace.span("probe", n_requests=len(todo)):
            if todo:
                self._probe_batch(
                    todo, queries, q_embs, memo, use_groups=use_groups, probe_impl=impl,
                    stats_memo=probe_stats,
                    delta_memo=delta_memo, dev_memo=dev_memo, dev_counts=dev_counts,
                )
            if trace is not None:
                # one child span per partition — the probe itself is fused
                # across partitions, so these carry the per-partition row
                # attribution (main vs delta) rather than separable time
                main_rows = [0] * n_models
                delta_rows = [0] * n_models
                for (mi, _qi, _p), rows in memo.items():
                    main_rows[mi] += int(rows.size)
                for (mi, _qi, _p), cnt in dev_counts.items():
                    main_rows[mi] += int(cnt)
                for (mi, _qi, _p), rows in delta_memo.items():
                    delta_rows[mi] += int(rows.size)
                for mi in range(n_models):
                    with obs_trace.span(
                        "partition",
                        part=mi,
                        main_rows=main_rows[mi],
                        delta_rows=delta_rows[mi],
                    ):
                        pass
        filter_time = time.perf_counter() - t0
        _M_STAGE_S.labels(stage="probe").observe(time.perf_counter() - t_plan)
        g_after = index_mod._GROUP_PAIRS.value
        l_after = index_mod._LEAF_PAIRS.value
        _M_FUNNEL.labels(stage="group_pairs").inc(g_after - pairs_before[0])
        _M_FUNNEL.labels(stage="leaf_pairs").inc(l_after - pairs_before[1])
        if trace is not None:
            trace.add_funnel(
                group_pairs=g_after - pairs_before[0],
                leaf_pairs=l_after - pairs_before[1],
            )
            surv = 0
            for sm in (probe_stats, stats_memo if cfg.plan_weight == "dr" else None):
                if sm:
                    surv += sum(int(e.get("surviving_groups", 0)) for e in sm.values())
            if use_groups:
                trace.add_funnel(surviving_groups=surv)
                _M_FUNNEL.labels(stage="surviving_groups").inc(surv)
        # ---- per-query candidate assembly -------------------------------
        t_asm = time.perf_counter()
        asm_span_cm = obs_trace.span("assemble")
        asm_span = asm_span_cm.__enter__()
        contributing: list[set] = [set() for _ in range(nq)]
        per_query_cands: list = []
        for qi, (q, plan) in enumerate(zip(queries, plans)):
            st = stats[qi]
            st.plan = plan
            candidates = [[] for _ in plan.paths]
            total_paths = 0
            for mi, model in enumerate(self.models):
                dp = delta.parts[mi] if delta is not None else None
                n_live = model.index.n_paths + (
                    dp.n_rows - dp.n_tombstones if dp is not None else 0
                )
                if n_live <= 0:
                    continue
                total_paths += n_live
                for pi, p in enumerate(plan.paths):
                    if device_assembly:
                        if dev_counts.get((mi, qi, p), 0):
                            contributing[qi].add(mi)
                    else:
                        rows = memo.get((mi, qi, p))
                        if rows is not None and rows.size:
                            candidates[pi].append(model.index.paths[rows])
                            contributing[qi].add(mi)
                    if dp is not None:
                        drows = delta_memo.get((mi, qi, p))
                        if drows is not None and drows.size:
                            candidates[pi].append(dp.paths[drows])
                            contributing[qi].add(mi)
            cand_arrays = []
            cand_total = 0
            for pi, parts in enumerate(candidates):
                if device_assembly:
                    # device rows straight from the probe; delta-buffer
                    # rows (host, small) ride along as one upload
                    ent = dev_memo.get((qi, plan.paths[pi]))
                    arr = self._device_candidates(ent, parts, len(plan.paths[pi]))
                    n_rows = arr[1]
                elif parts:
                    arr = np.concatenate(parts, axis=0)
                    n_rows = arr.shape[0]
                else:
                    arr = np.zeros((0, len(plan.paths[pi])), np.int32)
                    n_rows = 0
                cand_arrays.append(arr)
                cand_total += n_rows
                st.n_candidates[plan.paths[pi]] = int(n_rows)
            per_query_cands.append(cand_arrays)
            st.filter_time = filter_time / nq  # batch stage, amortized
            st.total_paths = total_paths * max(len(plan.paths), 1)
            st.candidate_paths = cand_total
            st.pruning_power = 1.0 - cand_total / max(st.total_paths, 1)
        batch_cands = sum(st.candidate_paths for st in stats)
        if asm_span is not None:
            asm_span.attrs["candidates"] = batch_cands
        asm_span_cm.__exit__(None, None, None)
        _M_STAGE_S.labels(stage="assemble").observe(time.perf_counter() - t_asm)
        _M_FUNNEL.labels(stage="candidates").inc(batch_cands)
        if trace is not None:
            trace.add_funnel(candidates=batch_cands)
        # ---- join + refine ----------------------------------------------
        # per-path candidates are duplicate-free (partitions are root-
        # disjoint; delta rows are disjoint from live main rows), so the
        # join may skip its dedup sorts (assume_unique)
        t_join0 = time.perf_counter()
        join_span_cm = obs_trace.span("join", impl=join_impl, n_queries=nq)
        join_span = join_span_cm.__enter__()
        if join_impl == "device":
            # one vmapped device program per join step for every group of
            # same-plan queries — the tick-level batched join
            t1 = time.perf_counter()
            results = match_from_candidates_many(
                self.graph, queries, [plan.paths for plan in plans], per_query_cands,
                induced=cfg.induced, join_impl="device", assume_unique=True,
            )
            join_time = time.perf_counter() - t1
            for qi, matches in enumerate(results):
                stats[qi].join_time = join_time / nq  # batch stage, amortized
                stats[qi].n_matches = len(matches)
        else:
            results = []
            for qi, (q, plan) in enumerate(zip(queries, plans)):
                t1 = time.perf_counter()
                matches = match_from_candidates(
                    self.graph, q, plan.paths, per_query_cands[qi],
                    induced=cfg.induced, join_impl="numpy", assume_unique=True,
                )
                stats[qi].join_time = time.perf_counter() - t1
                stats[qi].n_matches = len(matches)
                results.append(matches)
        n_matches = sum(len(m) for m in results)
        if join_span is not None:
            join_span.attrs["matches"] = n_matches
        join_span_cm.__exit__(None, None, None)
        _M_STAGE_S.labels(stage="join").observe(time.perf_counter() - t_join0)
        _M_FUNNEL.labels(stage="matches").inc(n_matches)
        if trace is not None:
            trace.add_funnel(matches=n_matches)
        return results, stats, contributing

    @staticmethod
    def _device_candidates(ent, host_parts: list, path_len: int):
        """Combine a probe's device candidate rows with host delta rows
        into one ``(rows, count)`` pair for the device join."""
        dev_rows, dev_cnt = ent if ent is not None else (None, 0)
        if not host_parts:
            if dev_rows is None:
                return np.zeros((0, path_len), np.int32), 0
            return dev_rows, dev_cnt
        extra = np.concatenate(host_parts, axis=0).astype(np.int32)
        if dev_cnt == 0:
            return jnp.asarray(extra), extra.shape[0]
        merged = jnp.concatenate([dev_rows[:dev_cnt], jnp.asarray(extra)], axis=0)
        return merged, dev_cnt + extra.shape[0]
