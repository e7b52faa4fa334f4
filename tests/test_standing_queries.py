"""Standing queries (serve/standing.py): at EVERY epoch of a random
update stream — edge/vertex add/remove, forced inline compaction,
background compaction installs, rebuild epochs — the accumulated
incremental match set (initial snapshot + applied deltas) must equal a
from-scratch ``match_many`` on the current graph, across ``index_kind``
× ``join_impl`` (and the int8 leaf sidecar).  Plus the cheap paths: untouched
subscriptions advance for free (no probe, no join), a tombstoned match
edge retracts the match, and the serving tiers (MatchServer tick
interleaving, MatchService async delivery with caps/shed/quarantine)
wire the registry through without losing or duplicating a delta."""
import asyncio

import numpy as np
import pytest

from repro.core import GnnPeConfig, GnnPeEngine, GraphUpdate, vf2_match
from repro.graphs import erdos_renyi, from_edge_list, random_connected_query
from repro.serve.admission import AdmissionConfig, TenantQuota
from repro.serve.faults import FaultSpec, FlakyEngine
from repro.serve.match_server import MatchServeConfig, MatchServer
from repro.serve.service import MatchService, ServiceConfig
from repro.serve.standing import StandingQueryRegistry


def _base_graph(seed: int = 5):
    return erdos_renyi(150, avg_degree=3.5, n_labels=4, seed=seed)


def _engine(g=None, **overrides):
    g = _base_graph() if g is None else g
    base = dict(
        n_partitions=3, encoder="monotone", n_multi=1, block_size=32, group_size=4
    )
    base.update(overrides)
    return GnnPeEngine(GnnPeConfig(**base)).build(g)


def _rand_update(rng, g, add=2, remove=2, add_vertices=0, remove_vertices=0):
    e = g.edge_array()
    kwargs = {}
    if remove and e.shape[0] > remove:
        kwargs["remove_edges"] = e[rng.choice(e.shape[0], size=remove, replace=False)]
    if add:
        kwargs["add_edges"] = rng.integers(0, g.n_vertices, size=(add, 2))
    if add_vertices:
        kwargs["add_vertex_labels"] = rng.integers(0, 4, size=add_vertices).astype(np.int32)
    if remove_vertices:
        kwargs["remove_vertices"] = rng.integers(0, g.n_vertices, size=remove_vertices)
    return GraphUpdate(**kwargs)


def _queries(g, n=3, seed0=50):
    out = []
    for s in range(n):
        try:
            out.append(random_connected_query(g, 4 + s % 3, seed=seed0 + s))
        except RuntimeError:
            continue
    assert out
    return out


def _apply_delta(acc: set, delta) -> set:
    """Apply one MatchDelta to a shadow accumulator, asserting delta
    consistency (no re-add of a held match, no retraction of an unknown
    one) — the subscriber-side contract."""
    added, retracted = set(delta.added), set(delta.retracted)
    assert not (added & acc), "delta re-added a match the subscriber already holds"
    assert retracted <= acc, "delta retracted a match the subscriber never had"
    return (acc - retracted) | added


# ------------------------------------------------ per-epoch identity ------


@pytest.mark.parametrize(
    "kind,join_impl,extra",
    [
        ("path", "numpy", {}),
        ("grouped", "numpy", {}),
        ("path", "device", {}),
        ("grouped", "device", {}),
        ("grouped", "device", {"quantize_index": True}),
    ],
)
def test_standing_equals_from_scratch_property(kind, join_impl, extra):
    """The headline gate: random update stream (edge add/remove, vertex
    add/remove, forced inline compaction at a tiny threshold), and at
    every epoch each subscription's accumulated set == match_many."""
    rng = np.random.default_rng(11)
    eng = _engine(
        index_kind=kind, join_impl=join_impl,
        delta_compact_min=8,  # force real compactions mid-stream
        **extra,
    )
    reg = StandingQueryRegistry(eng)
    qs = _queries(eng.graph)
    accs = {}
    for q in qs:
        sid, initial = reg.register(q)
        assert initial.epoch == 0 and not initial.retracted
        accs[sid] = _apply_delta(set(), initial)
    for ep in range(6):
        upd = _rand_update(
            rng, eng.graph,
            add_vertices=1 if ep % 2 else 0,
            remove_vertices=1 if ep == 3 else 0,
        )
        eng.apply_updates(upd)
        deltas = reg.on_epoch()
        for sid, q in zip(accs, qs):
            if sid in deltas:
                accs[sid] = _apply_delta(accs[sid], deltas[sid])
            ref = set(map(tuple, eng.match_many([q])[0]))
            assert accs[sid] == ref, f"epoch {ep + 1}: accumulated != from-scratch"
            assert set(reg.matches(sid)) == ref
    st = reg.stats()
    assert st["ticks"] == 6 and st["quarantined"] == 0


def test_standing_survives_background_compaction_install():
    """defer → snapshot → build → install between ticks must not perturb
    the incremental state (candidates are vertex paths, not row ids)."""
    rng = np.random.default_rng(3)
    eng = _engine(delta_compact_min=8)
    reg = StandingQueryRegistry(eng)
    qs = _queries(eng.graph)
    accs = {}
    for q in qs:
        sid, initial = reg.register(q)
        accs[sid] = set(initial.added)
    for ep in range(4):
        eng.apply_updates(_rand_update(rng, eng.graph), compaction="defer")
        if ep == 1:  # install mid-stream, after the epoch, before the tick
            for mi in eng.pending_compactions():
                snap = eng.prepare_compaction(mi)
                eng.install_compaction(snap, GnnPeEngine.build_compaction(snap))
        deltas = reg.on_epoch()
        for sid, q in zip(accs, qs):
            if sid in deltas:
                accs[sid] = _apply_delta(accs[sid], deltas[sid])
            assert accs[sid] == set(map(tuple, eng.match_many([q])[0]))
    assert eng.delta.n_compactions >= 1, "no compaction installed — test is vacuous"


def test_standing_full_refresh_on_rebuild_and_epoch_gap():
    """Rebuild epochs carry no fresh-row bookkeeping and a lagging
    subscription may miss ticks entirely — both must coalesce into one
    exact full-refresh diff."""
    rng = np.random.default_rng(9)
    eng = _engine()
    reg = StandingQueryRegistry(eng)
    (q,) = _queries(eng.graph, n=1)
    sid, initial = reg.register(q)
    acc = set(initial.added)
    # rebuild strategy: same graph change, every partition re-packed
    eng.apply_updates(_rand_update(rng, eng.graph), strategy="rebuild")
    deltas = reg.on_epoch()
    assert reg.subscription(sid).state.last_work == "full"
    if sid in deltas:
        acc = _apply_delta(acc, deltas[sid])
    assert acc == set(map(tuple, eng.match_many([q])[0]))
    # epoch gap: two delta epochs between ticks → one coalesced diff
    eng.apply_updates(_rand_update(rng, eng.graph))
    eng.apply_updates(_rand_update(rng, eng.graph))
    deltas = reg.on_epoch()
    assert reg.subscription(sid).state.last_work == "full"
    if sid in deltas:
        acc = _apply_delta(acc, deltas[sid])
    assert acc == set(map(tuple, eng.match_many([q])[0]))


# ------------------------------------------------------- cheap paths ------


def test_untouched_subscription_pays_nothing():
    """An update whose mutations miss a subscription's contributor
    partitions (and whose inserted paths' label hashes miss its plan)
    advances the subscription with last_work == "skip" — no probe, no
    join — and emits no delta."""
    # two disjoint 4-cycles with disjoint label alphabets, far apart in
    # partition space: a query over labels {0,1} never draws candidates
    # from the {2,3}-labeled component, and edits there hash-miss it
    edges = [(0, 1), (1, 2), (2, 3), (3, 0), (4, 5), (5, 6), (6, 7), (7, 4)]
    labels = np.array([0, 1, 0, 1, 2, 3, 2, 3], np.int32)
    g = from_edge_list(8, edges, labels)
    eng = _engine(g, n_partitions=2)
    reg = StandingQueryRegistry(eng)
    # the {0,1}-labeled 4-cycle itself (2-vertex queries sit below the
    # index path length l+1 = 3 and would match nothing)
    q = from_edge_list(
        4, [(0, 1), (1, 2), (2, 3), (3, 0)], np.array([0, 1, 0, 1], np.int32)
    )
    sid, initial = reg.register(q)
    assert initial.added, "query must match something for the test to bite"
    # edit strictly inside the other component
    eng.apply_updates(GraphUpdate(
        add_edges=np.array([[4, 6]]), remove_edges=np.array([[5, 6]])
    ))
    deltas = reg.on_epoch()
    sub = reg.subscription(sid)
    assert sub.state.last_work == "skip" and sub.n_skipped == 1
    assert sid not in deltas  # zero-cost epochs emit nothing
    assert sub.state.epoch == eng.epoch
    # and the skip was exact
    assert set(reg.matches(sid)) == set(map(tuple, eng.match_many([q])[0]))


def test_retraction_on_tombstone():
    """Removing an edge of a held match retracts exactly that match."""
    eng = _engine()
    reg = StandingQueryRegistry(eng)
    (q,) = _queries(eng.graph, n=1)
    sid, initial = reg.register(q)
    assert initial.added, "need at least one match to retract"
    victim = initial.added[0]
    # find a query edge and remove its image under the victim match
    qe = q.edge_array()
    u, v = int(victim[qe[0][0]]), int(victim[qe[0][1]])
    eng.apply_updates(GraphUpdate(remove_edges=np.array([[u, v]])))
    deltas = reg.on_epoch()
    assert sid in deltas and victim in set(deltas[sid].retracted)
    acc = _apply_delta(set(initial.added), deltas[sid])
    ref = set(map(tuple, eng.match_many([q])[0]))
    assert acc == ref
    assert victim not in ref
    # oracle cross-check: the engine itself is not the only referee
    assert ref == set(map(tuple, vf2_match(eng.graph, q)))


def test_registry_quarantines_deterministic_failures():
    """Poisoned evaluation quarantines after max_failures consecutive
    errors (terminal error delta); transient faults only retry."""
    eng = _engine()
    (q,) = _queries(eng.graph, n=1)
    rng = np.random.default_rng(0)
    flaky = FlakyEngine(eng, FaultSpec())  # no faults during registration
    reg = StandingQueryRegistry(flaky, max_failures=2)
    sid, _ = reg.register(q)
    # transient fault: retries next tick, never quarantines
    flaky.spec = FaultSpec(transient_on=(2,))  # call 1 was registration
    eng.apply_updates(_rand_update(rng, eng.graph))
    assert reg.on_epoch() == {} and reg.subscription(sid).failures == 1
    assert not reg.subscription(sid).quarantined
    assert reg.stats()["transient_errors"] == 1
    # healthy retry catches the lagging sub up and resets the streak
    flaky.spec = FaultSpec()
    reg.on_epoch()
    sub = reg.subscription(sid)
    assert sub.failures == 0 and sub.state.epoch == eng.epoch
    # deterministic poison: quarantined on the max_failures'th consecutive
    flaky.spec = FaultSpec(poison=lambda _q: True)
    eng.apply_updates(_rand_update(rng, eng.graph))
    assert reg.on_epoch() == {}  # failure 1 of 2: retry allowed
    deltas = reg.on_epoch()  # failure 2 of 2: terminal error delta
    sub = reg.subscription(sid)
    assert sub.quarantined and deltas[sid].error
    assert reg.stats()["quarantined"] == 1
    # quarantined subs never re-evaluate, even against a healthy engine
    flaky.spec = FaultSpec()
    assert reg.on_epoch() == {}


# ------------------------------------------------------- serving tiers ----


def test_match_server_interleaves_subscription_ticks():
    """Every update tick is followed by a subscription tick on the same
    thread; accumulated deltas == from-scratch at each served epoch."""
    rng = np.random.default_rng(21)
    eng = _engine()
    srv = MatchServer(eng, MatchServeConfig(max_batch=4, max_updates_per_tick=2))
    qs = _queries(eng.graph)
    sids = [srv.subscribe(q) for q in qs]
    for _ in range(3):
        srv.submit_update(_rand_update(rng, eng.graph))
        srv.submit_update(_rand_update(rng, eng.graph))
        srv.submit(qs[0])
        srv.step()  # one coalesced epoch + subscription tick + query tick
        for sid, q in zip(sids, qs):
            acc = set()
            for d in srv.match_deltas[sid]:
                acc = _apply_delta(acc, d)
            ref = set(map(tuple, eng.match_many([q])[0]))
            assert acc == ref
            assert srv.standing_matches(sid) == sorted(ref)
    assert srv.registry.counters["ticks"] == 3


def test_service_subscriptions_async_delivery_and_caps():
    """MatchService end to end: per-tenant subscription caps reject,
    deltas arrive on the handle's asyncio queue in epoch order, and the
    accumulated set equals from-scratch after drain."""
    eng = _engine()
    qs = _queries(eng.graph)

    async def run():
        svc = MatchService(
            eng,
            ServiceConfig(max_batch=4, idle_tick_s=0.02, backoff_base_s=0.005,
                          cache_fastpath=False),
            admission=AdmissionConfig(default_quota=TenantQuota(max_subscriptions=2)),
        )
        await svc.start()
        h0 = await svc.subscribe(qs[0], tenant="a")
        h1 = await svc.subscribe(qs[1], tenant="a")
        h_rej = await svc.subscribe(qs[2], tenant="a")  # over the cap
        h_b = await svc.subscribe(qs[2], tenant="b")  # other tenant fine
        assert h0.ok and h1.ok and h_b.ok
        assert h_rej.status == "rejected" and h_rej.reason == "tenant-subscriptions"
        rng = np.random.default_rng(5)
        for _ in range(3):
            svc.submit_update(_rand_update(rng, eng.graph))
            await svc.drain()
        # unsubscribe frees the cap slot
        assert await svc.unsubscribe(h1.sub_id)
        h_again = await svc.subscribe(qs[2], tenant="a")
        assert h_again.ok
        out = []
        for h, q in ((h0, qs[0]), (h_b, qs[2])):
            acc = set()
            while not h.deltas.empty():
                d = h.deltas.get_nowait()
                assert not d.error
                acc = _apply_delta(acc, d)
            out.append((acc, set(map(tuple, eng.match_many([q])[0]))))
        counters = dict(svc.counters)
        await svc.stop()
        return out, counters

    out, counters = asyncio.run(run())
    for acc, ref in out:
        assert acc == ref
    assert counters["subs_rejected"] == 1 and counters["subscribed"] == 4


def test_service_sheds_slow_subscriber():
    """A consumer that never drains its delta queue is shed — the
    subscription closes and admission releases the slot — instead of
    buffering without bound."""
    eng = _engine()
    (q,) = _queries(eng.graph, n=1)
    # a guaranteed-non-empty second delta: retract a known match by
    # tombstoning one of its edges (random churn can leave the match set
    # unchanged, and empty deltas are never delivered)
    qe = q.edge_array()
    m0 = sorted(map(tuple, eng.match_many([q])[0]))[0]
    u, v = int(m0[qe[0][0]]), int(m0[qe[0][1]])

    async def run():
        svc = MatchService(
            eng,
            ServiceConfig(max_batch=4, idle_tick_s=0.02, cache_fastpath=False,
                          max_deltas_buffered=1),
        )
        await svc.start()
        h = await svc.subscribe(q, tenant="slow")  # initial delta fills the buffer
        svc.submit_update(GraphUpdate(remove_edges=np.array([[u, v]])))
        await svc.drain()
        for _ in range(100):  # the overflow verdict lands via call_soon
            if not h.ok:
                break
            await asyncio.sleep(0.01)
        counters = dict(svc.counters)
        subs = svc.admission.subscriptions("slow")
        await svc.stop()
        return h, counters, subs

    h, counters, subs = asyncio.run(run())
    assert h.status == "shed" and h.reason == "delta-queue-full"
    assert counters["subs_shed"] == 1
    assert subs == 0  # cap slot released
