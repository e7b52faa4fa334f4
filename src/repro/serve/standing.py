"""Standing queries: continuous matching over the update stream.

A registered query gets a :class:`MatchDelta` (added/retracted matches)
pushed on every update tick instead of being re-matched from scratch.
The update stream is the event source, standing queries are the
watchers — the camwatcher → dispatcher pattern, with ``core/delta.py``'s
touched-partition/row bookkeeping deciding who wakes up.

Exactness argument (the headline CI gate checks it at every epoch):

* **Retractions.**  ``apply_graph_update`` marks both endpoints of every
  effectively changed edge (and every added/removed vertex) *touched*.
  A previously valid match can only become invalid if one of its edges
  changed, so every retracted match contains a touched vertex — the
  survivor filter (drop old matches containing a touched vertex, then
  re-derive the touched ones) misses nothing.
* **Additions.**  A match that is new at this epoch uses a changed edge,
  so it contains a touched vertex ``u``.  The plan's paths cover every
  query vertex, and the delta invariant (``main ∪ delta − tombstones``
  is exactly the current graph's path set) re-enumerates every graph
  path containing a touched vertex into this epoch's *fresh* delta rows
  (``FreshRows``) — so the plan path covering ``u`` joins through at
  least one fresh row.  Joining, for each plan position ``i`` with
  fresh candidates, ``old`` rows at positions ``< i``, ``fresh`` rows
  at ``i`` and ``old ∪ fresh`` at positions ``> i`` enumerates every
  touched match of the new graph exactly once (partition by the first
  fresh position; a match's row at each position is determined by its
  vertex assignment, and old/fresh rows are disjoint because fresh rows
  contain a touched vertex and cached old rows do not).
* **Cached candidates stay exact.**  The partition GNNs are frozen and
  an untouched vertex keeps its star neighborhood, so untouched rows
  keep their embeddings — the candidate set of a plan path changes only
  by (a) losing rows that contain a touched vertex and (b) gaining
  fresh rows that pass the same leaf dominance predicate the index
  probe applies.  Both are what the incremental step maintains, so the
  cached per-path candidate sets equal what a from-scratch probe at the
  current epoch would return (as sets of vertex paths — compaction only
  re-sorts rows and therefore never perturbs them).
* **Untouched subscriptions pay nothing.**  The affectedness test is the
  result cache's invalidation predicate (serve/cache.py): a subscription
  is affected only if a mutated partition contributed candidates, or a
  non-contributing mutated partition inserted paths whose label-sequence
  hash collides with one of the plan's.  If neither holds, no cached
  candidate or match contains a touched vertex and no fresh row can pass
  the label prefilter — state is exactly unchanged, so the subscription
  advances its epoch with a set intersection and no probe or join.
"""

from __future__ import annotations

import dataclasses

import jax
import numpy as np

from ..core.delta import probe_delta_multi, paths_touching
from ..core.index import hash_labels
from ..core.matcher import match_from_candidates, sort_matches
from ..obs.export import EVENTS
from ..obs.metrics import REGISTRY as _OBS

# the skip / incremental / full-refresh work ladder, cumulatively across
# all registries — the per-subscription split stays on Subscription
_M_STANDING = _OBS.counter(
    "gnnpe_standing_ticks_total",
    "Per-subscription tick outcomes on the standing-query work ladder",
    labels=("work",),
)

__all__ = [
    "MatchDelta",
    "StandingState",
    "StandingQueryRegistry",
    "advance_standing",
]


@dataclasses.dataclass(frozen=True)
class MatchDelta:
    """One epoch's incremental result for one standing query."""

    added: tuple  # match tuples new at this epoch, sorted
    retracted: tuple  # match tuples invalidated at this epoch, sorted
    epoch: int
    error: str = ""  # nonempty = terminal (subscription quarantined)

    @property
    def empty(self) -> bool:
        return not self.added and not self.retracted and not self.error


@dataclasses.dataclass
class StandingState:
    """Everything cached per standing query between update ticks.

    Candidates are stored as VERTEX paths (not row ids), per plan path
    per partition — stable across compaction, which re-sorts rows but
    never changes which vertex paths are live.
    """

    plan: object  # QueryPlan, frozen at registration (exactness is plan-independent)
    plan_hashes: frozenset  # label-sequence hash per plan path (affectedness test)
    qt: dict  # (mi, path) -> (q_emb, q_emb0, q_multi, label_hash) — frozen GNNs, so forever
    n_qv: int  # query vertex count
    epoch: int
    matches: np.ndarray  # (M, n_qv) int64 — current accumulated match set
    cands: list  # per plan path: {mi: (n, L) int32 candidate vertex paths}
    contributing: set  # partitions with any cached candidate row
    last_work: str = "full"  # "full" | "incremental" | "skip" | "noop"


# --------------------------------------------------------------------------
# Evaluation
# --------------------------------------------------------------------------


def _use_pallas(engine) -> bool:
    cfg = engine.cfg
    if cfg.use_pallas_scan is not None:
        return cfg.use_pallas_scan
    return jax.default_backend() == "tpu"


def _cat(per: dict, L: int) -> np.ndarray:
    """One candidate array per plan path: concat over partitions."""
    if not per:
        return np.zeros((0, L), np.int32)
    arrs = [per[mi] for mi in sorted(per)]
    return arrs[0] if len(arrs) == 1 else np.concatenate(arrs, axis=0)


def _match_set(engine, q, plan, cands) -> list:
    """Join + exact refine of the cached candidate sets (the same
    ``match_from_candidates`` the batch pipeline uses; per-path rows are
    duplicate-free — partitions are root-disjoint — so dedup sorts skip)."""
    cfg = engine.cfg
    cand_arrays = [_cat(cands[pi], len(p)) for pi, p in enumerate(plan.paths)]
    return match_from_candidates(
        engine.graph,
        q,
        plan.paths,
        cand_arrays,
        induced=cfg.induced,
        join_impl=cfg.join_impl,
        assume_unique=True,
    )


def _full_candidates(engine, q, plan):
    """From-scratch probe of every plan path — registration and the
    rebuild/epoch-gap fallback.  Returns ``(cands, cat)`` where ``cat``
    is the per-partition query-star embedding grid (reused for ``qt``)."""
    cfg = engine.cfg
    q_embs = engine._query_node_embeddings_many([q])
    cat, _spans = q_embs
    memo: dict = {}
    delta_memo: dict = {}
    engine._probe_batch(
        [(0, p) for p in plan.paths],
        [q],
        q_embs,
        memo,
        use_groups=cfg.index_kind == "grouped",
        delta_memo=delta_memo,
    )
    delta = engine.delta
    cands = []
    for p in plan.paths:
        per: dict = {}
        for mi, model in enumerate(engine.models):
            parts = []
            rows = memo.get((mi, 0, p))
            if rows is not None and rows.size:
                parts.append(model.index.paths[rows])
            if delta is not None:
                drows = delta_memo.get((mi, 0, p))
                if drows is not None and drows.size:
                    parts.append(delta.parts[mi].paths[drows])
            if parts:
                arr = parts[0] if len(parts) == 1 else np.concatenate(parts, axis=0)
                per[mi] = arr.astype(np.int32)
        cands.append(per)
    return cands, cat


def _query_tensors(engine, q, plan, cat) -> dict:
    """Per-(partition, plan-path) probe operands for future fresh-row
    scans.  The partition GNNs are frozen and these depend only on the
    query, so one computation at registration lasts the subscription's
    lifetime (rebuilds included)."""
    cfg = engine.cfg
    qt: dict = {}
    for p in plan.paths:
        pv = np.asarray(p, np.int64)
        qh = int(hash_labels(q.labels[pv][None, :])[0]) if cfg.quantize_index else None
        for mi in range(len(engine.models)):
            o, o0, om = cat[mi]
            q_multi = None
            if cfg.n_multi:
                q_multi = np.ascontiguousarray(om[:, pv].reshape(cfg.n_multi, -1))
            qt[(mi, p)] = (
                np.ascontiguousarray(o[pv].reshape(-1)),
                np.ascontiguousarray(o0[pv].reshape(-1)),
                q_multi,
                qh,
            )
    return qt


def _as_array(matches, n_qv: int) -> np.ndarray:
    if not len(matches):
        return np.zeros((0, n_qv), np.int64)
    return np.asarray(sort_matches(list(matches)), np.int64).reshape(-1, n_qv)


def _tuples(arr: np.ndarray) -> set:
    return {tuple(int(v) for v in row) for row in arr}


def _plan_hashes(q, plan) -> frozenset:
    return frozenset(
        int(hash_labels(q.labels[np.asarray(p, np.int64)][None, :])[0]) for p in plan.paths
    )


def _register(engine, q):
    plan = engine._deg_plan_cached(q)
    cands, cat = _full_candidates(engine, q, plan)
    matches = _match_set(engine, q, plan, cands)
    state = StandingState(
        plan=plan,
        plan_hashes=_plan_hashes(q, plan),
        qt=_query_tensors(engine, q, plan, cat),
        n_qv=q.n_vertices,
        epoch=engine.epoch,
        matches=_as_array(matches, q.n_vertices),
        cands=cands,
        contributing={mi for per in cands for mi in per},
        last_work="full",
    )
    added = tuple(sort_matches([tuple(int(v) for v in m) for m in matches]))
    return state, MatchDelta(added=added, retracted=(), epoch=engine.epoch)


def _refresh(engine, q, state: StandingState):
    """Full re-evaluation diffed against the accumulated set — the
    fallback for rebuild epochs and multi-epoch gaps (a lagging
    subscription that missed a tick, e.g. after a transient fault)."""
    cands, _cat_unused = _full_candidates(engine, q, state.plan)
    matches = _match_set(engine, q, state.plan, cands)
    new_set = {tuple(int(v) for v in m) for m in matches}
    old_set = _tuples(state.matches)
    state.cands = cands
    state.contributing = {mi for per in cands for mi in per}
    state.matches = _as_array(new_set, state.n_qv)
    state.epoch = engine.epoch
    state.last_work = "full"
    return state, MatchDelta(
        added=tuple(sorted(new_set - old_set)),
        retracted=tuple(sorted(old_set - new_set)),
        epoch=engine.epoch,
    )


def _affected(state: StandingState, mutated: dict) -> bool:
    """The result cache's invalidation predicate, applied to one
    subscription (see module docstring for why unaffected ⇒ unchanged)."""
    mut = {int(mi) for mi in mutated}
    if mut & state.contributing:
        return True
    inserted: set = set()
    for mi, info in mutated.items():
        if int(mi) in state.contributing:
            continue
        hashes = info.get("inserted_hashes")
        if hashes is not None:
            inserted.update(int(h) for h in np.asarray(hashes).reshape(-1))
    return bool(inserted & state.plan_hashes)


def _advance(engine, q, state: StandingState, upd: dict):
    """One incremental epoch step.  Commits to ``state`` only at the
    end, so an exception (e.g. an injected transient fault) leaves the
    previous epoch's state intact for a clean retry."""
    cfg = engine.cfg
    touched = np.asarray(upd["touched"], np.int64)
    mutated = upd["mutated"]
    fresh_map = upd["fresh"]
    plan_paths = state.plan.paths
    k = len(plan_paths)

    # 1. old candidates minus rows containing a touched vertex (only
    # mutated partitions can hold any — see _affected)
    old_cands: list = []
    for pi in range(k):
        per: dict = {}
        for mi, arr in state.cands[pi].items():
            if mi in mutated:
                keep = ~paths_touching(arr, touched)
                if not keep.all():
                    arr = arr[keep]
            if arr.shape[0]:
                per[mi] = arr
        old_cands.append(per)

    # 2. probe ONLY this epoch's fresh delta rows, all plan paths of a
    # partition batched as one probe item (one fused scan overall)
    fresh_cands: list = [dict() for _ in range(k)]
    items, meta = [], []
    for mi, fresh in fresh_map.items():
        sel = [pi for pi, p in enumerate(plan_paths) if len(p) == fresh.paths.shape[1]]
        if not sel:
            continue
        rows_q = [state.qt[(mi, plan_paths[pi])] for pi in sel]
        q_emb = np.stack([t[0] for t in rows_q])
        q_emb0 = np.stack([t[1] for t in rows_q])
        q_multi = np.stack([t[2] for t in rows_q], axis=1) if cfg.n_multi else None
        qh = np.asarray([t[3] for t in rows_q], np.int64) if cfg.quantize_index else None
        items.append((fresh, q_emb, q_emb0, q_multi, qh))
        meta.append((mi, sel))
    if items:
        out = probe_delta_multi(items, use_pallas=_use_pallas(engine))
        for (mi, sel), rows_list in zip(meta, out):
            for pi, rows in zip(sel, rows_list):
                if rows.size:
                    fresh_cands[pi][mi] = fresh_map[mi].paths[rows].astype(np.int32)

    # 3. touched matches of the new graph: partition by first fresh
    # position (old at < i, fresh at i, old ∪ fresh at > i) — each
    # touched match joins through exactly one of these products
    O = [_cat(old_cands[pi], len(plan_paths[pi])) for pi in range(k)]
    F = [_cat(fresh_cands[pi], len(plan_paths[pi])) for pi in range(k)]
    full = []
    for i in range(k):
        full.append(O[i] if F[i].shape[0] == 0 else np.concatenate([O[i], F[i]], axis=0))
    t_new: set = set()
    for i in range(k):
        if F[i].shape[0] == 0:
            continue
        cand = [O[j] if j < i else (F[j] if j == i else full[j]) for j in range(k)]
        ms = match_from_candidates(
            engine.graph,
            q,
            plan_paths,
            cand,
            induced=cfg.induced,
            join_impl=cfg.join_impl,
            assume_unique=True,
        )
        t_new.update(tuple(int(v) for v in m) for m in ms)

    # 4. diff against the accumulated set
    old = state.matches
    tmask = np.zeros(old.shape[0], bool)
    if old.shape[0] and touched.size:
        tmask = np.isin(old, touched).any(axis=1)
    survivors = old[~tmask]
    old_touched = _tuples(old[tmask])
    added = tuple(sorted(t_new - old_touched))
    retracted = tuple(sorted(old_touched - t_new))

    # 5. commit
    merged: list = []
    for pi in range(k):
        per = dict(old_cands[pi])
        for mi, arr in fresh_cands[pi].items():
            per[mi] = arr if mi not in per else np.concatenate([per[mi], arr], axis=0)
        merged.append(per)
    state.cands = merged
    state.contributing = {mi for per in merged for mi in per}
    new_rows = _as_array(t_new, state.n_qv)
    if survivors.shape[0] == 0:
        state.matches = new_rows
    elif new_rows.shape[0] == 0:
        state.matches = survivors
    else:
        state.matches = np.concatenate([survivors, new_rows])
    state.epoch = engine.epoch
    state.last_work = "incremental"
    return state, MatchDelta(added=added, retracted=retracted, epoch=engine.epoch)


def advance_standing(engine, q, state: StandingState | None = None):
    """Bring one standing query to the engine's current epoch.

    Returns ``(state, MatchDelta)``.  ``state=None`` registers (full
    evaluation, everything reported as added).  Otherwise the step is,
    in order of preference: nothing (already current), a free epoch
    bump (unaffected by this epoch's mutations), the incremental
    fresh-row path, or a full refresh (rebuild epochs and multi-epoch
    gaps).
    """
    if state is None:
        return _register(engine, q)
    if state.epoch == engine.epoch:
        state.last_work = "noop"
        return state, MatchDelta((), (), engine.epoch)
    upd = engine.epoch_fresh()
    if (
        upd is None
        or upd["epoch"] != engine.epoch
        or upd.get("strategy") != "delta"
        or state.epoch != engine.epoch - 1
    ):
        return _refresh(engine, q, state)
    mutated = upd["mutated"]
    if mutated and _affected(state, mutated):
        return _advance(engine, q, state, upd)
    state.epoch = engine.epoch
    state.last_work = "skip"
    return state, MatchDelta((), (), engine.epoch)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------


@dataclasses.dataclass
class Subscription:
    sub_id: int
    query: object
    state: StandingState | None
    callback: object = None  # callable(sub_id, MatchDelta) or None
    tenant: str = ""
    failures: int = 0  # consecutive, reset on success
    n_skipped: int = 0
    n_advanced: int = 0
    n_refreshed: int = 0
    quarantined: bool = False
    error: str = ""


class StandingQueryRegistry:
    """Standing queries over one engine's update stream.

    ``on_epoch()`` (the subscription tick) advances every active
    subscription to the engine's current epoch and returns the non-empty
    deltas; callbacks fire on the calling (engine) thread.  A
    subscription whose evaluation keeps failing deterministically is
    quarantined after ``max_failures`` consecutive errors — transient
    faults (``exc.transient``) only count as retries and never
    quarantine, mirroring the serving tier's retry/quarantine split.
    """

    def __init__(self, engine, max_failures: int = 3):
        self.engine = engine
        self.max_failures = max_failures
        self._subs: dict[int, Subscription] = {}
        self._next_id = 0
        self.counters = {
            "ticks": 0,
            "advanced": 0,
            "skipped": 0,
            "refreshed": 0,
            "quarantined": 0,
            "transient_errors": 0,
        }

    # ------------------------------------------------------------------
    def register(self, q, callback=None, tenant: str = "", sub_id: int | None = None) -> tuple:
        """Register a standing query; returns ``(sub_id, MatchDelta)``
        with the initial full evaluation as ``added`` (the callback is
        NOT invoked for it — the caller already holds the delta).

        ``sub_id`` pins the id — crash recovery re-registers journaled
        subscriptions under their original ids, so subscriber handles
        stay valid across a restart.  This registration path IS the
        full-refresh rung of the fallback ladder, taken exactly once:
        the returned delta carries the complete current match set."""
        state, delta = self.engine.match_incremental(q, None)
        if sub_id is None:
            sid = self._next_id
            self._next_id += 1
        else:
            sid = int(sub_id)
            if sid in self._subs:
                raise ValueError(f"subscription id {sid} already registered")
            self._next_id = max(self._next_id, sid + 1)
        self._subs[sid] = Subscription(
            sub_id=sid, query=q, state=state, callback=callback, tenant=tenant
        )
        return sid, delta

    def unregister(self, sub_id: int) -> bool:
        return self._subs.pop(sub_id, None) is not None

    def subscription(self, sub_id: int) -> Subscription:
        return self._subs[sub_id]

    def matches(self, sub_id: int) -> list:
        """The accumulated current match set, canonically ordered."""
        st = self._subs[sub_id].state
        if st is None:
            return []
        return sort_matches([tuple(int(v) for v in row) for row in st.matches])

    def lagging(self) -> bool:
        """Any active subscription behind the engine epoch (e.g. after a
        transient fault)?  The serving loop's heartbeat retries these."""
        epoch = self.engine.epoch
        return any(
            not s.quarantined and (s.state is None or s.state.epoch != epoch)
            for s in self._subs.values()
        )

    # ------------------------------------------------------------------
    def on_epoch(self) -> dict:
        """Advance every active subscription; returns {sub_id: MatchDelta}
        for the ones with changes (or a terminal quarantine error)."""
        out: dict[int, MatchDelta] = {}
        self.counters["ticks"] += 1
        epoch = self.engine.epoch
        for sid, sub in list(self._subs.items()):
            if sub.quarantined:
                continue
            if sub.state is not None and sub.state.epoch == epoch:
                continue
            try:
                sub.state, delta = self.engine.match_incremental(sub.query, sub.state)
            except Exception as exc:  # noqa: BLE001 — fault boundary per sub
                sub.failures += 1
                if getattr(exc, "transient", False):
                    # attempt-scoped: state is untouched, retry next tick
                    self.counters["transient_errors"] += 1
                    _M_STANDING.labels(work="transient-error").inc()
                    continue
                if sub.failures < self.max_failures:
                    continue
                sub.quarantined = True
                sub.error = f"{type(exc).__name__}: {exc}"
                self.counters["quarantined"] += 1
                _M_STANDING.labels(work="quarantined").inc()
                if EVENTS.active:
                    EVENTS.emit(
                        "quarantine", kind="standing", sub_id=sid,
                        tenant=sub.tenant, reason=sub.error,
                    )
                delta = MatchDelta((), (), epoch, error=sub.error)
                out[sid] = delta
                if sub.callback is not None:
                    sub.callback(sid, delta)
                continue
            sub.failures = 0
            work = sub.state.last_work
            if work == "skip":
                sub.n_skipped += 1
                self.counters["skipped"] += 1
                _M_STANDING.labels(work="skip").inc()
            elif work == "full":
                sub.n_refreshed += 1
                self.counters["refreshed"] += 1
                _M_STANDING.labels(work="full").inc()
            elif work == "incremental":
                sub.n_advanced += 1
                self.counters["advanced"] += 1
                _M_STANDING.labels(work="incremental").inc()
            if not delta.empty:
                out[sid] = delta
                if sub.callback is not None:
                    sub.callback(sid, delta)
        return out

    # ------------------------------------------------------------------
    def stats(self) -> dict:
        active = [s for s in self._subs.values() if not s.quarantined]
        return {
            "n_subscriptions": len(self._subs),
            "n_active": len(active),
            **self.counters,
        }
