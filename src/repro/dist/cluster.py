"""Multi-host cluster tier: scatter-gather matching over partition
owners (distributed GNN-PE, arXiv 2511.09052).

The single-process engine already shards the stacked probe over a
("part",) device mesh — but one process, one host, one result cache.
This module adds the missing tier:

    coordinator                      host 0 .. host H-1
    -----------                      ------------------
    plans (deg cache / dr round) --> probe owned partitions only
    scatter (qi, path) requests  --> (parts-scoped _probe_batch:
    gather candidate verts       <--  subset stack + delta + tombstones)
    assemble (ascending mi,
      main then delta)           --> join + refine at the coordinator

  * **Placement** — ``rebalance()`` feeds the engine's
    ``partition_stats()`` (the stacked probe's per-partition leaf-pair
    counters, candidate-row counts, rows, bytes) through the
    cost-ranked LPT placement of dist/placement.py; each host owns the
    partitions assigned to it.
  * **Identity** — hosts return exactly the candidate vertex arrays
    ``_match_many_core`` would gather locally (live main rows in index
    order, then delta rows), the coordinator assembles them in the same
    ascending-partition order and runs the same planner (shared plan
    cache) and join — so cluster ``match_many`` is byte-identical to
    single-process ``match_many`` at every delta epoch.
  * **Sharded cache** — ``ShardedResultCache`` homes each entry on the
    owner of its smallest contributing partition, so an update's
    invalidation stays local to the host that owns the mutated
    partition (serve/cache.py documents the split accounting).
  * **Host loss** — a host that dies mid-gather (``HostLostError``,
    which a wire timeout maps to) is re-probed by the coordinator over
    the lost host's partitions locally; matches are unaffected.
  * **Blue-green** — ``rebuild_generation`` snapshots, builds the next
    index generation off the serving path, persists it as a versioned
    artifact through dist/checkpoint.py's atomic ``CheckpointManager``,
    and installs under an epoch version check.

Process modes.  ``LocalHost`` simulates hosts in-process (the "local
cluster" fallback — same parts-scoped work a real host would do, minus
the wire).  ``ExchangeHost`` + ``serve_exchange_host`` speak an
atomic-rename npz protocol over a shared directory (``DirExchange``)
between real processes; ``init_distributed`` wires ``jax.distributed``
bootstrap when a multi-process launch provides a coordinator, falling
back to single-process local mode when it cannot.
"""

from __future__ import annotations

import io
import json
import os
import time
from pathlib import Path

import numpy as np

from ..core.index import hash_labels
from ..core.matcher import match_from_candidates, match_from_candidates_many
from ..core.planner import candidate_plan_paths, canonical_form
from ..graphs import Graph
from ..obs.export import EVENTS
from ..obs.metrics import REGISTRY as _OBS
from ..serve.cache import ShardedResultCache, canonical_matches, remap_matches
from .placement import DEFAULT_WEIGHTS, partition_costs, place_partitions

__all__ = [
    "HostLostError",
    "LocalHost",
    "ExchangeHost",
    "DirExchange",
    "serve_exchange_host",
    "ClusterEngine",
    "init_distributed",
]


class HostLostError(RuntimeError):
    """A host failed (or timed out) mid-gather; the coordinator
    re-probes its partitions locally."""


_M_CLUSTER = _OBS.counter(
    "gnnpe_cluster_events_total",
    "Cluster control/data-plane events since process start",
    labels=("event",),
)


def init_distributed(
    num_processes: int = 1,
    process_id: int = 0,
    coordinator_address: str | None = None,
    timeout_s: float = 60.0,
) -> dict:
    """``jax.distributed`` bootstrap with a single-process fallback.

    With ``num_processes > 1`` and a coordinator address, tries
    ``jax.distributed.initialize`` (gRPC coordination service) so every
    process shares one cluster view; any failure — no coordinator, an
    unsupported backend, a second initialize — degrades to local mode
    instead of raising, because the scatter-gather data plane does not
    depend on it (DirExchange carries the candidates either way).
    """
    if num_processes <= 1:
        return {"mode": "local", "num_processes": 1, "process_id": 0}
    try:
        import jax

        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            initialization_timeout=int(timeout_s),
        )
        return {
            "mode": "distributed",
            "num_processes": num_processes,
            "process_id": process_id,
        }
    except Exception as exc:  # pragma: no cover - backend/version specific
        return {
            "mode": "local",
            "num_processes": num_processes,
            "process_id": process_id,
            "error": repr(exc),
        }


# ---------------------------------------------------------------------------
# hosts
# ---------------------------------------------------------------------------
class LocalHost:
    """One simulated host of the local cluster: probes its owned
    partitions through the engine's parts-scoped path (subset stack,
    delta buffers, tombstones) — the same work scoping a separate
    process would do, minus the wire.  ``fail_next`` injects a loss for
    the re-scatter tests."""

    def __init__(self, host_id: int, engine):
        self.host_id = int(host_id)
        self.engine = engine
        self.owned: list = []
        self.fail_next = False

    def probe(self, queries, requests, return_stats: bool = False):
        if self.fail_next:
            self.fail_next = False
            raise HostLostError(f"host {self.host_id} lost mid-gather")
        return self.engine.probe_candidates(
            queries, requests, parts=self.owned, return_stats=return_stats
        )


class DirExchange:
    """Shared-directory blob exchange — the 2-process smoke's data
    plane.  Writes stage to a tmp file, fsync, ``os.replace`` into
    place, then fsync the *directory* (the durable-rename contract: the
    replace itself is atomic against concurrent readers, but only the
    dir fsync pins the name→inode update across a power cut — without
    it a crashed writer can reboot into a directory where the blob it
    acknowledged never existed).  Blobs are CRC-framed npz payloads
    (durability/wal.py framing): a reader that races bit rot or a
    truncated copy gets a typed rejection up front instead of an
    arbitrary failure mid-``np.load``."""

    def __init__(self, root):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)

    def put(self, key: str, meta: dict | None = None, arrays: dict | None = None) -> None:
        from ..durability.wal import frame_payload

        payload = {f"a_{k}": np.asarray(v) for k, v in (arrays or {}).items()}
        payload["__meta__"] = np.asarray(json.dumps(meta or {}))
        buf = io.BytesIO()
        np.savez(buf, **payload)
        final = self.root / f"{key}.npz"
        tmp = final.with_suffix(final.suffix + f".tmp{os.getpid()}")
        with open(tmp, "wb") as f:
            f.write(frame_payload(buf.getvalue()))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, final)
        dfd = os.open(self.root, os.O_RDONLY)
        try:
            os.fsync(dfd)
        finally:
            os.close(dfd)

    def get(self, key: str, timeout: float = 60.0, poll: float = 0.01):
        from ..durability.wal import CorruptRecordError, unframe_payload

        final = self.root / f"{key}.npz"
        deadline = time.monotonic() + timeout
        while not final.exists():
            if time.monotonic() > deadline:
                raise HostLostError(f"timed out waiting for {key}")
            time.sleep(poll)
        try:
            blob = unframe_payload(final.read_bytes())
        except CorruptRecordError as e:
            # a torn/corrupt blob means the peer (or its disk) is gone —
            # surface it as the host-loss the coordinator already heals
            raise HostLostError(f"corrupt exchange blob {key}: {e}") from e
        with np.load(io.BytesIO(blob), allow_pickle=False) as z:
            meta = json.loads(str(z["__meta__"]))
            arrays = {k[2:]: z[k] for k in z.files if k.startswith("a_")}
        return meta, arrays


def _pack_queries(queries: list) -> tuple[dict, dict]:
    meta = {"nq": len(queries)}
    arrays = {}
    for i, q in enumerate(queries):
        arrays[f"q{i}_offsets"] = q.offsets
        arrays[f"q{i}_nbrs"] = q.nbrs
        arrays[f"q{i}_labels"] = q.labels
    return meta, arrays


def _unpack_queries(meta: dict, arrays: dict) -> list:
    return [
        Graph(
            np.asarray(arrays[f"q{i}_offsets"], np.int64),
            np.asarray(arrays[f"q{i}_nbrs"], np.int32),
            np.asarray(arrays[f"q{i}_labels"], np.int32),
        )
        for i in range(int(meta["nq"]))
    ]


def _pack_candidates(cands: dict) -> tuple[dict, dict]:
    keys = []
    arrays = {}
    for i, ((mi, qi, p), (main, dverts)) in enumerate(cands.items()):
        keys.append([int(mi), int(qi), [int(v) for v in p]])
        arrays[f"k{i}_m"] = main
        arrays[f"k{i}_d"] = dverts
    return {"keys": keys}, arrays


def _unpack_candidates(meta: dict, arrays: dict) -> dict:
    out = {}
    for i, (mi, qi, p) in enumerate(meta["keys"]):
        out[(int(mi), int(qi), tuple(int(v) for v in p))] = (
            np.asarray(arrays[f"k{i}_m"], np.int32),
            np.asarray(arrays[f"k{i}_d"], np.int32),
        )
    return out


class ExchangeHost:
    """Proxy for a host in another process: scatter writes
    ``req_<host>_<n>`` blobs, the remote ``serve_exchange_host`` loop
    answers ``resp_<host>_<n>``.  The parts to probe ride inside each
    request, so worker and coordinator need no placement
    synchronization; a timeout maps to ``HostLostError`` and the
    coordinator re-probes locally."""

    def __init__(self, host_id: int, exchange: DirExchange, timeout: float = 120.0):
        self.host_id = int(host_id)
        self.exchange = exchange
        self.timeout = float(timeout)
        self.owned: list = []
        self._seq = 0

    def probe(self, queries, requests, return_stats: bool = False):
        meta, arrays = _pack_queries(queries)
        meta["requests"] = [[int(qi), [int(v) for v in p]] for qi, p in requests]
        meta["parts"] = [int(mi) for mi in self.owned]
        meta["return_stats"] = bool(return_stats)
        rid = self._seq
        self._seq += 1
        self.exchange.put(f"req_{self.host_id}_{rid}", meta, arrays)
        rmeta, rarrays = self.exchange.get(
            f"resp_{self.host_id}_{rid}", timeout=self.timeout
        )
        cands = _unpack_candidates(rmeta, rarrays)
        if return_stats:
            stats = {
                (int(mi), int(qi), tuple(int(v) for v in p)): st
                for mi, qi, p, st in rmeta.get("stats", [])
            }
            return cands, stats
        return cands

    def stop(self) -> None:
        self.exchange.put(f"req_{self.host_id}_{self._seq}", {"stop": True}, {})
        self._seq += 1


def serve_exchange_host(
    engine, host_id: int, exchange: DirExchange, max_requests: int | None = None,
    timeout: float = 120.0,
) -> int:
    """Worker-process loop: answer the coordinator's probe requests for
    ``host_id`` until a stop blob (or silence past ``timeout``) arrives.
    Returns the number of requests served.  The worker holds a
    deterministic replica of the engine (same seed ⇒ identical build),
    so its parts-scoped candidates equal the coordinator's own."""
    n = 0
    while max_requests is None or n < max_requests:
        try:
            meta, arrays = exchange.get(f"req_{host_id}_{n}", timeout=timeout)
        except HostLostError:
            return n
        if meta.get("stop"):
            return n
        queries = _unpack_queries(meta, arrays)
        requests = [(int(qi), tuple(int(v) for v in p)) for qi, p in meta["requests"]]
        out = engine.probe_candidates(
            queries, requests, parts=meta["parts"],
            return_stats=bool(meta.get("return_stats", False)),
        )
        cands, st = out if meta.get("return_stats") else (out, None)
        rmeta, rarrays = _pack_candidates(cands)
        if st is not None:
            rmeta["stats"] = [
                [int(mi), int(qi), [int(v) for v in p], d]
                for (mi, qi, p), d in st.items()
            ]
        exchange.put(f"resp_{host_id}_{n}", rmeta, rarrays)
        n += 1
    return n


# ---------------------------------------------------------------------------
# the cluster engine
# ---------------------------------------------------------------------------
class ClusterEngine:
    """Scatter-gather ``match_many`` over partition-owner hosts.

    ``ClusterEngine(engine, n_hosts=4)`` simulates a 4-host local
    cluster; pass ``hosts=[...]`` (e.g. ``ExchangeHost`` proxies) to
    span processes.  The coordinator keeps the full engine — it plans,
    embeds queries, assembles gathered candidates and joins; hosts do
    the parts-scoped probe work.  ``cache_capacity > 0`` adds the
    partition-owner-sharded result cache.
    """

    def __init__(
        self,
        engine,
        n_hosts: int | None = None,
        hosts: list | None = None,
        cache_capacity: int = 0,
        weights: tuple = DEFAULT_WEIGHTS,
        durability=None,
    ):
        if hosts is None:
            hosts = [LocalHost(h, engine) for h in range(max(int(n_hosts or 1), 1))]
        if not hosts:
            raise ValueError("a cluster needs at least one host")
        self.engine = engine
        self.hosts = list(hosts)
        self.weights = weights
        self.placement = None
        # coordinator-side durability: the coordinator owns the engine, so
        # it journals the update stream exactly like a MatchServer would
        self.durability = None
        if durability is not None:
            from ..durability.manager import Durability

            self.durability = (
                durability if isinstance(durability, Durability) else Durability(durability)
            )
            if (
                self.durability.cfg.genesis_snapshot
                and self.durability.snapshots.latest_epoch() is None
            ):
                self.durability.snapshot(engine)
        self.cache = (
            ShardedResultCache(len(self.hosts), cache_capacity) if cache_capacity else None
        )
        self.stats = {"host_losses": 0, "scatter_rounds": 0, "requests_scattered": 0}
        self.rebalance()

    @property
    def n_hosts(self) -> int:
        return len(self.hosts)

    def rebalance(self):
        """(Re)compute the cost-ranked partition→host placement from the
        engine's current ``partition_stats()`` and install it on the
        hosts and the cache's owner map."""
        costs = partition_costs(self.engine.partition_stats(), self.weights)
        self.placement = place_partitions(costs, len(self.hosts))
        for h, host in enumerate(self.hosts):
            host.owned = self.placement.owned(h)
        if self.cache is not None:
            self.cache.set_placement(self.placement.host_of)
        _M_CLUSTER.labels(event="rebalance").inc()
        if EVENTS.active:
            EVENTS.emit(
                "rebalance",
                n_hosts=len(self.hosts),
                owned=[list(self.placement.owned(h)) for h in range(len(self.hosts))],
            )
        return self.placement

    # ------------------------------------------------------------- probes --
    def _scatter(self, queries: list, requests: list, return_stats: bool = False):
        """One probe round: fan ``requests`` to every owning host,
        gather the merged candidate dict.  A lost host's partitions are
        re-probed locally by the coordinator — matches unaffected."""
        gathered: dict = {}
        stats: dict | None = {} if return_stats else None
        self.stats["scatter_rounds"] += 1
        self.stats["requests_scattered"] += len(requests)
        _M_CLUSTER.labels(event="scatter_round").inc()
        _M_CLUSTER.labels(event="request_scattered").inc(len(requests))
        for host in self.hosts:
            if not host.owned:
                continue
            try:
                out = host.probe(queries, requests, return_stats=return_stats)
            except HostLostError:
                self.stats["host_losses"] += 1
                _M_CLUSTER.labels(event="host_loss").inc()
                if EVENTS.active:
                    EVENTS.emit(
                        "host_loss",
                        host=getattr(host, "host_id", None),
                        n_owned=len(host.owned),
                        reprobed_locally=True,
                    )
                out = self.engine.probe_candidates(
                    queries, requests, parts=host.owned, return_stats=return_stats
                )
            if return_stats:
                cands, st = out
                stats.update(st)
            else:
                cands = out
            gathered.update(cands)
        return (gathered, stats) if return_stats else gathered

    # -------------------------------------------------------------- match --
    def match(self, q, **kw):
        return self.match_many([q], **kw)[0]

    def match_many(self, queries: list, return_stats: bool = False):
        """Scatter-gather exact matching; byte-identical per query to
        single-process ``engine.match_many`` (see module doc)."""
        eng = self.engine
        nq = len(queries)
        if nq == 0:
            return ([], []) if return_stats else []
        results: list = [None] * nq
        info: list = [{} for _ in range(nq)]
        canon = None
        miss = list(range(nq))
        if self.cache is not None:
            canon = [canonical_form(q) for q in queries]
            miss = []
            for qi, (perm, key) in enumerate(canon):
                ent = self.cache.get(key)
                if ent is not None:
                    results[qi] = remap_matches(ent.matches, perm)
                    info[qi] = {"cache_hit": True, "n_matches": len(results[qi])}
                else:
                    miss.append(qi)
        if miss:
            sub = [queries[qi] for qi in miss]
            sub_results, contributing, plans = self._match_scatter(sub)
            for k, qi in enumerate(miss):
                results[qi] = sub_results[k]
                info[qi] = {"cache_hit": False, "n_matches": len(sub_results[k])}
                if self.cache is not None:
                    q = queries[qi]
                    perm, key = canon[qi]
                    plan_hashes = {
                        int(hash_labels(q.labels[np.asarray(p, np.int64)][None, :])[0])
                        for p in plans[k].paths
                    }
                    self.cache.put(
                        key,
                        canonical_matches(sub_results[k], perm, q.n_vertices),
                        contributing[k],
                        plan_hashes,
                        eng.epoch,
                    )
        return (results, info) if return_stats else results

    def _match_scatter(self, queries: list):
        """The core scatter-gather pipeline for cache-miss queries:
        plan (shared plan cache; dr cost probes are their own scatter
        round) → scatter plan paths → assemble in ascending partition
        order (main rows then delta rows — ``_match_many_core``'s exact
        order) → join at the coordinator."""
        eng = self.engine
        cfg = eng.cfg
        nq = len(queries)
        n_models = len(eng.models)
        use_groups = cfg.index_kind == "grouped"
        gathered: dict = {}
        gstats: dict = {}
        probed: set = set()
        # ---- plans: replicate _match_many_core byte for byte ------------
        plan_group_size = cfg.group_size if (cfg.plan_weight == "dr" and use_groups) else 1
        cached_plans: list = [None] * nq
        weight_fns: list = [None] * nq
        if cfg.plan_weight == "dr":
            cached_plans = [eng._dr_plan_peek(q, plan_group_size) for q in queries]
            reqs = list(
                dict.fromkeys(
                    (qi, p)
                    for qi, q in enumerate(queries)
                    if cached_plans[qi] is None
                    for p in candidate_plan_paths(q, cfg.path_length)
                )
            )
            if reqs:
                out = self._scatter(queries, reqs, return_stats=use_groups)
                if use_groups:
                    cands, st = out
                    gstats.update(st)
                else:
                    cands = out
                gathered.update(cands)
                probed.update(reqs)
            gsz = max(cfg.group_size, 1)

            def make_weight_fn(qi):
                # same weights as the single-process dr cost model: the
                # gathered arrays ARE the memo/delta rows (grouped adds
                # surviving-group fan-outs from the ride-along stats)
                def weight_fn(p):
                    if use_groups:
                        w = sum(
                            gstats[(mi, qi, p)]["surviving_groups"]
                            for mi in range(n_models)
                            if (mi, qi, p) in gstats
                        )
                        w += sum(
                            -(-gathered[(mi, qi, p)][1].shape[0] // gsz)
                            for mi in range(n_models)
                            if (mi, qi, p) in gathered
                        )
                        return float(w)
                    return float(
                        sum(
                            gathered[(mi, qi, p)][0].shape[0]
                            + gathered[(mi, qi, p)][1].shape[0]
                            for mi in range(n_models)
                            if (mi, qi, p) in gathered
                        )
                    )

                return weight_fn

            weight_fns = [
                make_weight_fn(qi) if cached_plans[qi] is None else None
                for qi in range(nq)
            ]
        plans = [
            cached_plans[qi]
            if cached_plans[qi] is not None
            else eng._plan_cached(q, weight_fn=weight_fns[qi], group_size=plan_group_size)
            for qi, q in enumerate(queries)
        ]
        # ---- retrieval scatter: plan paths not already gathered ----------
        todo = list(
            dict.fromkeys(
                (qi, p)
                for qi, plan in enumerate(plans)
                for p in plan.paths
                if (qi, p) not in probed
            )
        )
        if todo:
            gathered.update(self._scatter(queries, todo))
            probed.update(todo)
        # ---- assembly: the single-process candidate order, exactly -------
        # host join: ascending mi, main rows then delta rows per partition
        # (_match_many_core's loop).  device join: the engine's probe
        # assembles mains on device in ascending SLOT order with every
        # partition's delta rows appended after — so the coordinator
        # mirrors that order for byte-identity there too.
        device_assembly = cfg.join_impl == "device" and n_models > 0
        if device_assembly:
            slot_of = eng.stacked_probe().stacked.slot_of
            main_order = sorted(range(n_models), key=lambda mi: int(slot_of[mi]))
        else:
            main_order = list(range(n_models))
        contributing: list = [set() for _ in range(nq)]
        per_query_cands: list = []
        for qi, plan in enumerate(plans):
            candidates: list = [[] for _ in plan.paths]
            for mi in main_order:
                for pi, p in enumerate(plan.paths):
                    ent = gathered.get((mi, qi, p))
                    if ent is None:
                        continue
                    main, dverts = ent
                    if main.shape[0]:
                        candidates[pi].append(main)
                        contributing[qi].add(mi)
                    if not device_assembly and dverts.shape[0]:
                        candidates[pi].append(dverts)
                        contributing[qi].add(mi)
            if device_assembly:
                for mi in range(n_models):
                    for pi, p in enumerate(plan.paths):
                        ent = gathered.get((mi, qi, p))
                        if ent is not None and ent[1].shape[0]:
                            candidates[pi].append(ent[1])
                            contributing[qi].add(mi)
            per_query_cands.append(
                [
                    np.concatenate(parts, axis=0)
                    if parts
                    else np.zeros((0, len(plan.paths[pi])), np.int32)
                    for pi, parts in enumerate(candidates)
                ]
            )
        # ---- join + refine at the coordinator ---------------------------
        if cfg.join_impl == "device":
            results = match_from_candidates_many(
                eng.graph, queries, [plan.paths for plan in plans], per_query_cands,
                induced=cfg.induced, join_impl="device", assume_unique=True,
            )
        else:
            results = [
                match_from_candidates(
                    eng.graph, q, plans[qi].paths, per_query_cands[qi],
                    induced=cfg.induced, join_impl="numpy", assume_unique=True,
                )
                for qi, q in enumerate(queries)
            ]
        return results, contributing, plans

    # ------------------------------------------------------------ updates --
    def apply_updates(self, updates, **kw) -> dict:
        """Updates land on the engine; invalidation routes through the
        sharded cache so evictions stay on the mutated partitions' owner
        shards.  (Process mode: every process applies the same update
        stream — deterministic replicas stay identical.)"""
        if self.durability is not None:
            if not isinstance(updates, (list, tuple)):
                updates = [updates]
            self.durability.log_epoch(
                self.engine.epoch + 1,
                list(updates),
                kw.get("strategy", "delta"),
                kw.get("compaction", "inline"),
            )
        summary = self.engine.apply_updates(updates, **kw)
        if self.durability is not None:
            self.durability.after_apply(self.engine)
        if self.cache is not None:
            last = self.engine.epoch_fresh() or {}
            if last.get("strategy") == "rebuild":
                self.cache.clear()
            else:
                mutated = last.get("mutated") or {}
                if mutated:
                    self.cache.invalidate(mutated)
        return summary

    # --------------------------------------------------------- blue-green --
    def rebuild_generation(self, store=None, max_attempts: int = 3) -> dict:
        """Blue-green index swap: snapshot → build the next generation
        off the serving path → persist it as versioned artifacts
        (``store``: a dist/checkpoint.py ``CheckpointManager``; one
        ``step_<generation>.npz`` per generation) → version-checked
        atomic install.  An update landing mid-build fails the install;
        re-snapshot and retry, bounded by ``max_attempts``."""
        eng = self.engine
        snap = None
        for _ in range(max(int(max_attempts), 1)):
            snap = eng.prepare_generation()
            built = eng.build_generation(snap)
            if store is not None:
                store.save(int(snap["generation"]), _generation_artifacts(built))
            if eng.install_generation(snap, built):
                _M_CLUSTER.labels(event="generation_installed").inc()
                if EVENTS.active:
                    EVENTS.emit(
                        "blue_green_swap",
                        generation=int(snap["generation"]),
                        installed=True,
                    )
                return {"generation": int(snap["generation"]), "installed": True}
            _M_CLUSTER.labels(event="generation_install_conflict").inc()
        if EVENTS.active:
            EVENTS.emit(
                "blue_green_swap",
                generation=int(snap["generation"]),
                installed=False,
            )
        return {"generation": int(snap["generation"]), "installed": False}

    def load_generation(self, store, generation: int | None = None) -> dict:
        """Verified read-back of persisted generation artifacts.

        ``store.restore_arrays`` runs the digest-manifest verification
        (dist/checkpoint.py) — a torn or bit-flipped artifact raises
        ``CorruptCheckpointError`` instead of installing a wrong index;
        ``generation=None`` falls back to the newest *valid* step.  The
        arrays re-pack through ``build_index`` exactly as
        ``_generation_artifacts`` promises → ``{"generation", "indexes"}``.
        """
        from ..core.grouping import attach_groups
        from ..core.index import build_index

        arrays, gen = store.restore_arrays(generation)
        eng = self.engine
        indexes = []
        for mi, m in enumerate(eng.models):
            paths = np.asarray(arrays[f"p{mi}_paths"], np.int32)
            quantize = m.index.emb_q is not None
            ix = build_index(
                paths,
                np.asarray(arrays[f"p{mi}_emb"], np.float32),
                np.asarray(arrays[f"p{mi}_emb0"], np.float32),
                np.asarray(arrays[f"p{mi}_emb_multi"], np.float32),
                block_size=m.index.block_size,
                fanout=m.index.fanout,
                quantize=quantize,
                path_labels=eng.graph.labels[paths] if quantize and paths.size else None,
            )
            if m.index.groups is not None:
                attach_groups(ix, m.index.groups.group_size)
            indexes.append(ix)
        return {"generation": int(gen), "indexes": indexes}

    # ------------------------------------------------------------- status --
    def cluster_stats(self) -> dict:
        out = {
            "n_hosts": len(self.hosts),
            "placement": self.placement.as_dict() if self.placement else None,
            **self.stats,
        }
        if self.cache is not None:
            out["cache"] = self.cache.stats_dict()
        return out

    def shutdown(self) -> None:
        """Stop remote worker loops (no-op for local hosts)."""
        for host in self.hosts:
            stop = getattr(host, "stop", None)
            if stop is not None:
                stop()


def _generation_artifacts(built: list) -> dict:
    """Flatten a built generation to plain arrays for the artifact
    store: per partition the packed paths + main/label/multi path
    embeddings — enough to re-pack the exact index via ``build_index``
    on restore (levels/groups/quantization are deterministic functions
    of these under the engine config)."""
    art = {}
    for mi, out in enumerate(built):
        ix = out["index"]
        art[f"p{mi}_paths"] = ix.paths
        art[f"p{mi}_emb"] = ix.emb
        art[f"p{mi}_emb0"] = ix.emb0
        art[f"p{mi}_emb_multi"] = ix.emb_multi
    return art
