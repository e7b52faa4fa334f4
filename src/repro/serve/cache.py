"""Query-result cache keyed on WL-canonical query signatures, with
partition-scoped invalidation (the distributed GNN-PE follow-up's
cache-optimization layer).

Keying.  ``planner.canonical_form`` already computes a deterministic
label/degree canonical ordering for plan caching; equal keys guarantee
identical canonical graphs, so two (even relabeled-isomorphic) queries
with the same key have the same matches *up to the vertex relabeling*.
Entries therefore store matches in canonical vertex order
(``canonical_matches``) and every hit maps them back through the
querying graph's own permutation (``remap_matches``) — a repeat of an
isomorphic query skips the whole filter + join + refine pipeline.

Partition-scoped invalidation.  Each entry records

  * ``contributing`` — the partitions (engine model indices) that
    contributed candidate rows to the original computation, and
  * ``plan_hashes``  — the label-sequence hashes of its plan paths.

An update that mutates partitions ``M`` evicts an entry iff

  1. a contributing partition was mutated (``M ∩ contributing ≠ ∅``) —
     deletions or insertions there can remove or add matches; or
  2. a *non*-contributing partition gained delta paths whose
     label-sequence hash collides with one of the entry's plan-path
     hashes — the only way a partition that previously produced zero
     candidates can start producing them, since a candidate must pass
     the Lemma 4.1 label-embedding equality (the same
     distinct-labels ⇒ distinct-hash assumption the §Perf C2 quantized
     leaf pre-filter already relies on).

Everything else survives: updates far from an entry's candidate space
leave it servable, and compaction (a pure re-sort) invalidates nothing.
Entries are LRU-evicted beyond ``capacity``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from ..obs.metrics import REGISTRY as _OBS

# process-wide cumulative mirrors of the per-instance CacheStats /
# sharded eviction splits (repro.obs) — exported via /metrics
_M_CACHE_EVENTS = _OBS.counter(
    "gnnpe_cache_events_total",
    "Result-cache events (hits, misses, insertions, invalidated, evicted)",
    labels=("event",),
)
_M_CACHE_EVICT = _OBS.counter(
    "gnnpe_cache_shard_evictions_total",
    "ShardedResultCache evictions by locality scope",
    labels=("scope",),
)

__all__ = [
    "ResultCache",
    "ShardedResultCache",
    "CacheStats",
    "canonical_matches",
    "remap_matches",
]


def canonical_matches(matches: list, perm: np.ndarray, n_vertices: int) -> np.ndarray:
    """Match tuples (indexed by query vertex) → (M, n) canonical-order array."""
    if not matches:
        return np.zeros((0, n_vertices), np.int32)
    arr = np.asarray(matches, np.int32).reshape(len(matches), n_vertices)
    return arr[:, perm]


def remap_matches(arr: np.ndarray, perm: np.ndarray) -> list:
    """Canonical-order match array → tuples for a query with ordering ``perm``."""
    out = np.empty_like(arr)
    out[:, perm] = arr
    return [tuple(int(x) for x in r) for r in out]


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    insertions: int = 0
    invalidated: int = 0  # entries evicted by update invalidation
    evicted: int = 0  # entries evicted by the capacity bound

    def __setattr__(self, name: str, value) -> None:
        # mirror every increment into the registry counter — the
        # per-instance fields stay authoritative for existing callers
        delta = value - getattr(self, name, 0)
        if delta > 0:
            _M_CACHE_EVENTS.labels(event=name).inc(delta)
        object.__setattr__(self, name, value)

    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    def as_dict(self) -> dict:
        return {**dataclasses.asdict(self), "hit_rate": self.hit_rate()}


@dataclasses.dataclass
class _Entry:
    matches: np.ndarray  # (M, n) int32, canonical vertex order
    contributing: frozenset  # partition (model) indices that produced candidates
    plan_hashes: frozenset  # label-sequence hashes of the entry's plan paths
    epoch: int  # index epoch the entry was computed at
    plan: object = None  # QueryPlan in canonical vertex ids (for hit-side stats)


class ResultCache:
    def __init__(self, capacity: int = 2048):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._entries: dict[bytes, _Entry] = {}  # insertion order = LRU order
        self._by_part: dict[int, set] = {}  # partition -> keys it contributed to
        self.stats = CacheStats()

    def __len__(self) -> int:
        return len(self._entries)

    # ------------------------------------------------------------------
    def get(self, key: bytes, record: bool = True) -> _Entry | None:
        """``record=False`` is a peek: hit/miss counters are left to the
        caller (the serving fast path counts its own hits and would
        otherwise double-count the pipeline's miss)."""
        ent = self._entries.get(key)
        if ent is None:
            if record:
                self.stats.misses += 1
            return None
        # LRU touch: re-append at the back of the insertion order
        del self._entries[key]
        self._entries[key] = ent
        if record:
            self.stats.hits += 1
        return ent

    def put(
        self,
        key: bytes,
        matches: np.ndarray,
        contributing,
        plan_hashes,
        epoch: int,
        plan=None,
    ) -> None:
        if key in self._entries:
            self._drop(key)
        while len(self._entries) >= self.capacity:
            self._drop(next(iter(self._entries)))
            self.stats.evicted += 1
        ent = _Entry(
            matches=matches,
            contributing=frozenset(int(p) for p in contributing),
            plan_hashes=frozenset(int(h) for h in plan_hashes),
            epoch=int(epoch),
            plan=plan,
        )
        self._entries[key] = ent
        for p in ent.contributing:
            self._by_part.setdefault(p, set()).add(key)
        self.stats.insertions += 1

    # ------------------------------------------------------------------
    def invalidate(self, mutated: dict, eager_rule1: bool = True) -> int:
        """Evict entries an update batch could have staled.

        ``mutated``: partition (model) index → ``{"deleted": bool,
        "inserted_hashes": iterable of int label-sequence hashes}`` for
        every partition the update touched.  Returns the eviction count.

        ``eager_rule1=False`` runs only rule 2 (the label-hash collision
        check) — the sharded cluster cache sends non-owner shards that
        reduced form and catches rule 1 lazily at ``get`` instead (see
        ``ShardedResultCache``).
        """
        if not mutated or not self._entries:
            return 0
        victims = set()
        inserted: set = set()
        for mi, info in mutated.items():
            if eager_rule1:
                victims |= self._by_part.get(int(mi), set())
            hashes = info.get("inserted_hashes")
            if hashes is not None:
                inserted.update(int(h) for h in np.asarray(hashes).reshape(-1))
        if inserted:
            mut = set(int(mi) for mi in mutated)
            for key, ent in self._entries.items():
                if key in victims:
                    continue
                # a non-contributing mutated partition can add candidates
                # only via label-compatible new paths
                if (mut - ent.contributing) and (ent.plan_hashes & inserted):
                    victims.add(key)
        for key in victims:
            self._drop(key)
        self.stats.invalidated += len(victims)
        return len(victims)

    def clear(self) -> None:
        self._entries.clear()
        self._by_part.clear()

    # ------------------------------------------------------------------
    def _drop(self, key: bytes) -> None:
        ent = self._entries.pop(key, None)
        if ent is None:
            return
        for p in ent.contributing:
            keys = self._by_part.get(p)
            if keys is not None:
                keys.discard(key)
                if not keys:
                    del self._by_part[p]


class ShardedResultCache:
    """Partition-owner-sharded ``ResultCache`` (the cluster tier).

    One ``ResultCache`` shard per host.  An entry is homed on the shard
    of the host that owns its smallest contributing partition — for the
    common partition-local workload (every candidate from one host's
    partitions) that IS the host holding the entry's data.

    Invalidation stays owner-local by construction: an update mutating
    partitions ``M`` eagerly invalidates (rule 1 + rule 2) only the
    shards of hosts owning some partition in ``M``.  Entries on *other*
    shards that contributed a mutated partition are not chased with
    cross-host eviction traffic — each ``invalidate`` bumps a per-
    partition mutation tick (O(n_partitions) replicated metadata), and
    ``get`` drops an entry lazily when any contributing partition
    mutated after the entry was inserted.  Rule 2 (a non-contributing
    partition gaining delta paths whose label hash collides with the
    entry's plan) is the one case lazy ticks cannot cover, so it alone
    is broadcast — and only when the update inserted paths at all.
    The eviction split is accounted:

      * ``local_evictions``  — eager evictions on a mutated partition's
        owner shard (the invalidation the cluster keeps host-local);
      * ``remote_evictions`` — rule-2 hash-collision evictions on
        non-owner shards (the only eager cross-host evictions left);
      * ``lazy_evictions``   — stale entries dropped at ``get`` by the
        coordinator's tick check (read-side work, never cross-host).

    Collision-free update streams therefore evict with
    ``remote_evictions == 0`` — asserted in tests/test_cluster.py.  The
    key→shard directory is
    maintained on put and lazily pruned on get (shards drop entries
    internally via LRU/invalidation).
    """

    def __init__(self, n_shards: int, capacity: int = 2048):
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        self.shards = [ResultCache(capacity) for _ in range(n_shards)]
        self._home: dict[bytes, int] = {}  # key -> homed shard id
        self._tick_of: dict[bytes, int] = {}  # key -> tick at insertion
        self.host_of = np.zeros(0, np.int64)  # model index -> owning host
        self.last_mutated = np.zeros(0, np.int64)  # model index -> mutation tick
        self._tick = 0
        self.stats = CacheStats()  # cluster-level hit/miss accounting
        self.local_evictions = 0
        self.remote_evictions = 0
        self.lazy_evictions = 0

    def __len__(self) -> int:
        return sum(len(s) for s in self.shards)

    @property
    def n_shards(self) -> int:
        return len(self.shards)

    def set_placement(self, host_of) -> None:
        """Install the partition→host ownership map (model index order).
        Existing entries keep serving from their old shard — the
        directory finds them — and re-home on their next put."""
        self.host_of = np.asarray(host_of, np.int64)

    def home_shard(self, contributing) -> int:
        """The shard an entry with these contributing partitions homes
        on: owner of the smallest contributing model index (0 when
        nothing contributed or no placement is installed)."""
        cont = [int(mi) for mi in contributing if int(mi) < self.host_of.size]
        if not cont:
            return 0
        return int(self.host_of[min(cont)]) % len(self.shards)

    # ------------------------------------------------------------------
    def get(self, key: bytes, record: bool = True):
        sid = self._home.get(key)
        if sid is None:
            if record:
                self.stats.misses += 1
            return None
        ent = self.shards[sid].get(key, record=False)
        if ent is None:  # shard dropped it (LRU/invalidation); prune lazily
            del self._home[key]
            self._tick_of.pop(key, None)
            if record:
                self.stats.misses += 1
            return None
        t0 = self._tick_of.get(key, 0)
        for mi in ent.contributing:
            # rule 1, evaluated lazily: a contributing partition mutated
            # after this entry was cached (eager eviction ran only on the
            # mutated partitions' owner shards)
            if mi < self.last_mutated.size and self.last_mutated[mi] > t0:
                self.shards[sid]._drop(key)
                del self._home[key]
                self._tick_of.pop(key, None)
                self.lazy_evictions += 1
                _M_CACHE_EVICT.labels(scope="lazy").inc()
                if record:
                    self.stats.misses += 1
                return None
        if record:
            self.stats.hits += 1
        return ent

    def put(self, key: bytes, matches, contributing, plan_hashes, epoch, plan=None) -> int:
        """Insert on the entry's home shard; returns the shard id."""
        sid = self.home_shard(contributing)
        old = self._home.get(key)
        if old is not None and old != sid:
            self.shards[old]._drop(key)
        self.shards[sid].put(key, matches, contributing, plan_hashes, epoch, plan=plan)
        self._home[key] = sid
        self._tick_of[key] = self._tick
        self.stats.insertions += 1
        return sid

    # ------------------------------------------------------------------
    def invalidate(self, mutated: dict) -> int:
        """Eagerly invalidate only the mutated partitions' owner shards;
        bump mutation ticks so other shards' stale entries fall to the
        lazy ``get`` check (see class doc)."""
        if not mutated:
            return 0
        self._tick += 1
        hi = max(int(mi) for mi in mutated)
        if hi >= self.last_mutated.size:
            grown = np.zeros(hi + 1, np.int64)
            grown[: self.last_mutated.size] = self.last_mutated
            self.last_mutated = grown
        for mi in mutated:
            self.last_mutated[int(mi)] = self._tick
        owners = {
            int(self.host_of[int(mi)]) % len(self.shards)
            for mi in mutated
            if int(mi) < self.host_of.size
        }
        inserted = any(
            info.get("inserted_hashes") is not None
            and np.asarray(info["inserted_hashes"]).size
            for info in mutated.values()
        )
        total = 0
        for sid, shard in enumerate(self.shards):
            if sid in owners:
                n = shard.invalidate(mutated)
                if n:
                    self.local_evictions += n
                    _M_CACHE_EVICT.labels(scope="local").inc(n)
            elif inserted:
                n = shard.invalidate(mutated, eager_rule1=False)
                if n:
                    self.remote_evictions += n
                    _M_CACHE_EVICT.labels(scope="remote").inc(n)
            else:
                n = 0
            total += n
        self.stats.invalidated += total
        return total

    def clear(self) -> None:
        for s in self.shards:
            s.clear()
        self._home.clear()
        self._tick_of.clear()

    # ------------------------------------------------------------------
    def locality(self) -> dict:
        """The invalidation-locality split the cluster bench gates on."""
        total = self.local_evictions + self.remote_evictions
        return {
            "local_evictions": self.local_evictions,
            "remote_evictions": self.remote_evictions,
            "lazy_evictions": self.lazy_evictions,
            "local_fraction": self.local_evictions / total if total else 1.0,
        }

    def stats_dict(self) -> dict:
        return {
            **self.stats.as_dict(),
            **self.locality(),
            "shard_sizes": [len(s) for s in self.shards],
        }
