"""Crash-safe durability (repro/durability/ + hardened dist/checkpoint.py).

The contract under test: a restarted replica recovered from newest valid
snapshot + WAL-suffix replay is **byte-identical** to a replica that
never crashed (``engine_fingerprint`` + ``match_many`` equality), at
every kill point and under torn-write/bit-flip corruption — or recovery
fails loudly with a typed error.  Never a silently wrong answer.
"""
import dataclasses
import json
import os

import numpy as np
import pytest

from repro.core import GnnPeConfig, GnnPeEngine, GraphUpdate
from repro.dist.checkpoint import CheckpointManager, CorruptCheckpointError
from repro.dist.cluster import DirExchange, HostLostError
from repro.durability import (
    CorruptRecordError,
    CorruptWalError,
    CrashPoint,
    Durability,
    DurabilityConfig,
    RecoveryError,
    SimulatedCrash,
    WriteAheadLog,
    engine_fingerprint,
    engine_state,
    flip_byte,
    frame_payload,
    recover_engine,
    recover_server,
    restore_engine,
    scrub_engine,
    unframe_payload,
)
from repro.durability.snapshot import _META_KEY
from repro.durability.wal import decode_record, encode_record
from repro.graphs import erdos_renyi, random_connected_query
from repro.serve.match_server import MatchServeConfig, MatchServer

# ------------------------------------------------------------------ base ---

CONFIGS = {
    "path-stacked": dict(index_kind="path"),
    "grouped-stacked": dict(index_kind="grouped"),
}


def _graph(seed: int = 5):
    return erdos_renyi(150, avg_degree=3.5, n_labels=4, seed=seed)


def _build(g, **overrides):
    cfg = GnnPeConfig(
        n_partitions=3, encoder="monotone", n_multi=1, block_size=32,
        group_size=4, **overrides,
    )
    return GnnPeEngine(cfg).build(g)


@pytest.fixture(scope="module")
def base():
    """One build per config, kept as an in-memory snapshot so every test
    clones a byte-identical replica instead of re-running the offline
    stage."""
    g = _graph()
    out = {}
    for name, kw in CONFIGS.items():
        eng = _build(g, **kw)
        meta, arrays = engine_state(eng)
        out[name] = (meta, arrays)
    return g, out


def _clone(base_entry):
    meta, arrays = base_entry
    eng, _ = restore_engine({**arrays, _META_KEY: np.asarray(json.dumps(meta))})
    return eng


def _stream(g, k, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(k):
        e = g.edge_array()
        out.append(
            GraphUpdate(
                add_edges=rng.integers(0, g.n_vertices, size=(2, 2)),
                remove_edges=e[rng.choice(e.shape[0], size=1, replace=False)],
            )
        )
    return out


def _queries(g, n=3, seed0=50):
    return [random_connected_query(g, 4, seed=seed0 + s) for s in range(n)]


def _identical(a, b, queries):
    return engine_fingerprint(a) == engine_fingerprint(b) and (
        a.match_many(queries) == b.match_many(queries)
    )


# ------------------------------------------------------------- WAL units ---


def test_frame_roundtrip_and_rejection():
    payload = b"hello wal"
    assert unframe_payload(frame_payload(payload)) == payload
    with pytest.raises(CorruptRecordError):
        unframe_payload(b"GW")  # short header
    with pytest.raises(CorruptRecordError):
        unframe_payload(b"XXXX" + frame_payload(payload)[4:])  # bad magic
    blob = bytearray(frame_payload(payload))
    blob[-1] ^= 0xFF
    with pytest.raises(CorruptRecordError):
        unframe_payload(bytes(blob))  # CRC
    with pytest.raises(CorruptRecordError):
        unframe_payload(frame_payload(payload)[:-3])  # torn payload


def test_record_codec_roundtrip():
    arrays = {
        "a": np.arange(6, dtype=np.int64).reshape(3, 2),
        "b": np.zeros((0, 2), np.int64),
        "c": np.array([1.5, -2.5], np.float32),
    }
    rec = decode_record(encode_record("epoch", {"epoch": 7, "s": "x"}, arrays))
    assert rec.type == "epoch" and rec.meta == {"epoch": 7, "s": "x"} and rec.epoch == 7
    for k, v in arrays.items():
        assert rec.arrays[k].dtype == v.dtype
        assert np.array_equal(rec.arrays[k], v)
    empty = decode_record(encode_record("unsub", {"sub_id": 1}))
    assert empty.arrays == {} and empty.epoch is None
    with pytest.raises(CorruptRecordError):
        decode_record(encode_record("epoch", {}, arrays)[:-4])


def test_graph_update_array_roundtrip():
    u = GraphUpdate(
        add_edges=[(1, 2), (3, 4)],
        remove_edges=np.array([[5, 6]]),
        add_vertex_labels=np.array([0, 2], np.int32),
        remove_vertices=[9],
    )
    r = GraphUpdate.from_arrays(u.to_arrays())
    for k, v in u.to_arrays().items():
        assert np.array_equal(v, r.to_arrays()[k]) and r.to_arrays()[k].dtype == v.dtype
    e = GraphUpdate.from_arrays(GraphUpdate().to_arrays())
    assert e.to_arrays()["add_edges"].shape == (0, 2)


def test_wal_append_reopen_rotate(tmp_path):
    w = WriteAheadLog(tmp_path, segment_bytes=700)
    info = w.open()
    assert info == {"records": 0, "truncated_bytes": 0, "segments": 0}
    for i in range(8):
        w.append("epoch", {"epoch": i + 1}, {"x": np.full((4, 2), i, np.int64)})
    assert len(w.segments()) > 1  # rotated by size
    assert w.last_epoch() == 8
    w.close()

    w2 = WriteAheadLog(tmp_path, segment_bytes=700)
    assert w2.open()["records"] == 8
    recs = w2.records()
    assert [r.epoch for r in recs] == list(range(1, 9))
    assert np.array_equal(recs[3].arrays["x"], np.full((4, 2), 3, np.int64))
    w2.append("epoch", {"epoch": 9})
    assert w2.last_epoch() == 9
    w2.close()


def test_wal_torn_tail_truncates_and_resumes(tmp_path):
    w = WriteAheadLog(tmp_path)
    w.open()
    for i in range(5):
        w.append("epoch", {"epoch": i + 1}, {"x": np.arange(8)})
    w.close()
    seg = w.segments()[-1][1]
    with open(seg, "r+b") as f:
        f.truncate(seg.stat().st_size - 9)  # torn mid-frame

    w2 = WriteAheadLog(tmp_path)
    info = w2.open()
    assert info["records"] == 4 and info["truncated_bytes"] > 0
    w2.append("epoch", {"epoch": 5})  # resumes at the last durable epoch
    assert [r.epoch for r in w2.records()] == [1, 2, 3, 4, 5]
    w2.close()


def test_wal_midstream_corruption_fails_loudly(tmp_path):
    w = WriteAheadLog(tmp_path)
    w.open()
    for i in range(5):
        w.append("epoch", {"epoch": i + 1}, {"x": np.arange(32)})
    w.close()
    seg = w.segments()[-1][1]
    flip_byte(seg, offset=seg.stat().st_size // 3)  # damage an early record
    with pytest.raises(CorruptWalError):
        WriteAheadLog(tmp_path).open()


def test_wal_corrupt_sealed_segment_fails_loudly(tmp_path):
    w = WriteAheadLog(tmp_path, segment_bytes=400)
    w.open()
    for i in range(6):
        w.append("epoch", {"epoch": i + 1}, {"x": np.arange(16)})
    w.close()
    assert len(w.segments()) >= 2
    first = w.segments()[0][1]
    with open(first, "r+b") as f:  # torn-looking tail in a NON-final segment
        f.truncate(first.stat().st_size - 5)
    with pytest.raises(CorruptWalError):
        WriteAheadLog(tmp_path, segment_bytes=400).open()


def test_wal_prune_keeps_uncovered_and_active(tmp_path):
    w = WriteAheadLog(tmp_path)
    w.open()
    for i in range(4):
        w.append("epoch", {"epoch": i + 1})
        w.rotate()
    w.append("epoch", {"epoch": 5})
    dropped = w.prune(2)  # snapshot at epoch 2 supersedes epochs 1-2
    assert dropped == 2
    assert [r.epoch for r in w.records()] == [3, 4, 5]
    assert w.prune(100) == 2  # sealed 3,4 go; active segment never does
    assert [r.epoch for r in w.records()] == [5]
    w.close()


# ------------------------------------------- checkpoint hardening (sat 1) ---


def _save_steps(tmp_path, steps=(1, 2)):
    mgr = CheckpointManager(tmp_path, keep=8)
    for s in steps:
        mgr.save(s, {"w": np.arange(64, dtype=np.float64) * s, "b": np.ones(3) * s})
    return mgr


def test_checkpoint_missing_step(tmp_path):
    mgr = _save_steps(tmp_path)
    with pytest.raises(CorruptCheckpointError):
        mgr.verify_step(99)
    with pytest.raises(FileNotFoundError):
        CheckpointManager(tmp_path / "empty").restore_arrays()


def test_checkpoint_truncated_file(tmp_path):
    mgr = _save_steps(tmp_path)
    p = mgr._path(2)
    with open(p, "r+b") as f:
        f.truncate(p.stat().st_size // 2)
    with pytest.raises(CorruptCheckpointError):
        mgr.restore_arrays(step=2)  # explicit step: strict
    arrays, step = mgr.restore_arrays()  # step=None: newest VALID
    assert step == 1 and np.array_equal(arrays["b"], np.ones(3))
    assert mgr.latest_step() == 1 and mgr.valid_steps() == [1]


def test_checkpoint_flipped_byte(tmp_path):
    mgr = _save_steps(tmp_path)
    flip_byte(mgr._path(2), offset=-20)
    with pytest.raises(CorruptCheckpointError):
        mgr.restore_arrays(step=2)
    _, step = mgr.restore_arrays()
    assert step == 1


def test_checkpoint_missing_manifest_invalidates(tmp_path):
    mgr = _save_steps(tmp_path)
    os.unlink(mgr._manifest_path(2))  # crashed before the manifest commit
    assert mgr.latest_step() == 1
    _, step = mgr.restore_arrays()
    assert step == 1


# --------------------------------------------------- snapshot round trips ---


@pytest.mark.parametrize("name", list(CONFIGS))
def test_snapshot_byte_identity(base, name):
    g, entries = base
    eng = _clone(entries[name])
    for u in _stream(g, 3, seed=1):
        eng.apply_updates([u])
    rt = _clone(engine_state(eng))  # snapshot round trip of the dirty engine
    assert _identical(eng, rt, _queries(g))
    # determinism survives the round trip: one more identical epoch each
    u = _stream(g, 1, seed=9)[0]
    eng.apply_updates([u])
    rt.apply_updates([u])
    assert engine_fingerprint(eng) == engine_fingerprint(rt)


def test_snapshot_corruption_falls_back(base, tmp_path):
    g, entries = base
    eng = _clone(entries["path-stacked"])
    dur = Durability(DurabilityConfig(str(tmp_path), genesis_snapshot=False))
    dur.snapshot(eng)
    eng.apply_updates([_stream(g, 1)[0]])
    dur.snapshot(eng)
    newest = dur.snapshots.mgr._path(eng.epoch)
    flip_byte(newest, offset=-50)
    restored, meta, _, epoch = dur.snapshots.load()
    assert epoch == 0  # fell back past the damaged snapshot
    with pytest.raises(CorruptCheckpointError):
        dur.snapshots.load(step=eng.epoch)
    dur.close()


# -------------------------------------- crash-injection identity sweep -----


def _run_until_crash(eng, durability, stream):
    srv = MatchServer(eng, MatchServeConfig(durability=durability))
    for u in stream:
        srv.submit_update(u)
        try:
            srv.apply_update_tick()
        except SimulatedCrash as e:
            return srv, e.point
    return srv, None


@pytest.mark.parametrize("name", list(CONFIGS))
@pytest.mark.parametrize("point,at", [
    ("before_log", 3),
    ("after_log", 5),       # logged but never applied: replay must cover it
    ("after_apply", 4),
    ("mid_snapshot", 2),    # npz committed, manifest missing: step skipped
    ("after_snapshot", 2),  # snapshot committed, rotate/prune never ran
])
def test_crash_recovery_identity(base, tmp_path, name, point, at):
    g, entries = base
    stream = _stream(g, 7, seed=3)
    queries = _queries(g)

    victim = _clone(entries[name])
    dur = Durability(
        DurabilityConfig(str(tmp_path), snapshot_every=3),
        crash=CrashPoint(point, at=at),
    )
    _, crashed_at = _run_until_crash(victim, dur, stream)
    assert crashed_at == point

    recovered, info = recover_engine(DurabilityConfig(str(tmp_path), snapshot_every=3))
    control = _clone(entries[name])
    for u in stream[: info["epoch"]]:
        control.apply_updates([u])
    assert _identical(recovered, control, queries), f"{name}/{point}@{at}"

    # the recovered replica keeps serving: apply the rest of the stream
    for u in stream[info["epoch"] :]:
        recovered.apply_updates([u])
        control.apply_updates([u])
    assert engine_fingerprint(recovered) == engine_fingerprint(control)


def test_crash_then_torn_write_recovers(base, tmp_path):
    """SIGKILL mid-append: the torn tail is dropped, recovery lands on the
    last durable epoch — a state the no-crash replica also passed through."""
    g, entries = base
    stream = _stream(g, 5, seed=4)
    victim = _clone(entries["path-stacked"])
    dur = Durability(
        DurabilityConfig(str(tmp_path), snapshot_every=0, genesis_snapshot=False),
        crash=CrashPoint("after_log", at=4),
    )
    dur.snapshot(victim)
    _run_until_crash(victim, dur, stream)
    seg = sorted((tmp_path / "wal").glob("seg_*.wal"))[-1]
    with open(seg, "r+b") as f:
        f.truncate(seg.stat().st_size - 7)  # epoch-4 record torn mid-frame

    recovered, info = recover_engine(DurabilityConfig(str(tmp_path)))
    assert info["epoch"] == 3 and info["truncated_bytes"] > 0
    control = _clone(entries["path-stacked"])
    for u in stream[:3]:
        control.apply_updates([u])
    assert _identical(recovered, control, _queries(g))


def test_crash_recovery_corrupt_wal_fails_loudly(base, tmp_path):
    g, entries = base
    victim = _clone(entries["path-stacked"])
    dur = Durability(DurabilityConfig(str(tmp_path), snapshot_every=0))
    srv = MatchServer(victim, MatchServeConfig(durability=dur))
    for u in _stream(g, 4, seed=6):
        srv.submit_update(u)
        srv.apply_update_tick()
    dur.close()
    seg = sorted((tmp_path / "wal").glob("seg_*.wal"))[-1]
    flip_byte(seg, offset=seg.stat().st_size // 4)
    with pytest.raises((CorruptWalError, RecoveryError)):
        recover_engine(DurabilityConfig(str(tmp_path)))


def test_recovery_without_snapshot_fails_loudly(tmp_path):
    with pytest.raises(RecoveryError):
        recover_engine(DurabilityConfig(str(tmp_path / "nothing")))


def test_recovery_rejects_wal_gap(base, tmp_path):
    g, entries = base
    victim = _clone(entries["path-stacked"])
    dur = Durability(DurabilityConfig(str(tmp_path), snapshot_every=0, genesis_snapshot=False))
    dur.snapshot(victim)
    for u in _stream(g, 3, seed=8):
        dur.log_epoch(victim.epoch + 1, [u], "delta", "inline")
        victim.apply_updates([u])
        dur.wal.rotate()  # one epoch per segment
    dur.close()
    segs = sorted((tmp_path / "wal").glob("seg_*.wal"))
    os.unlink(segs[1])  # epoch 2 vanishes: contiguity broken
    with pytest.raises(RecoveryError):
        recover_engine(DurabilityConfig(str(tmp_path)))


# ------------------------------------------- standing-query recovery edge ---


def test_standing_reregistration_exactly_once(base, tmp_path):
    """Recovery re-registers each subscription with its original id and
    takes the full-refresh rung exactly once: one initial delta, no
    duplicates, and the accumulated set equals the from-scratch oracle
    across the crash and beyond it."""
    g, entries = base
    stream = _stream(g, 6, seed=12)
    queries = _queries(g, n=2, seed0=70)

    victim = _clone(entries["grouped-stacked"])
    dur = Durability(
        DurabilityConfig(str(tmp_path), snapshot_every=3),
        crash=CrashPoint("after_apply", at=5),
    )
    srv = MatchServer(victim, MatchServeConfig(durability=dur))
    sids = [srv.subscribe(q) for q in queries]
    accs = {sid: set(srv.standing_matches(sid)) for sid in sids}
    for u in stream:
        srv.submit_update(u)
        try:
            srv.apply_update_tick()
        except SimulatedCrash:
            break

    rec_srv, info = recover_server(DurabilityConfig(str(tmp_path), snapshot_every=3))
    assert sorted(info["subscriptions"]) == sorted(sids)  # original ids survive
    oracle = _clone(entries["grouped-stacked"])
    for u in stream[: info["epoch"]]:
        oracle.apply_updates([u])
    refs = oracle.match_many(queries)
    for sid, ref in zip(sids, refs):
        # exactly one delta: the registration-time full refresh
        assert len(rec_srv.match_deltas[sid]) == 1
        assert rec_srv.standing_matches(sid) == sorted(set(ref))

    # beyond the crash: incremental deltas must still replay to the oracle
    for u in stream[info["epoch"] :]:
        rec_srv.submit_update(u)
        rec_srv.apply_update_tick()
        oracle.apply_updates([u])
    refs = oracle.match_many(queries)
    for sid, ref in zip(sids, refs):
        acc = set(rec_srv.standing_matches(sid))
        got = set()
        for d in rec_srv.match_deltas[sid]:
            got = (got - set(d.retracted)) | set(d.added)
        assert acc == got == {tuple(int(v) for v in m) for m in ref}


def test_unsubscribe_survives_recovery(base, tmp_path):
    g, entries = base
    victim = _clone(entries["path-stacked"])
    dur = Durability(DurabilityConfig(str(tmp_path), snapshot_every=0))
    srv = MatchServer(victim, MatchServeConfig(durability=dur))
    q1, q2 = _queries(g, n=2, seed0=90)
    s1, s2 = srv.subscribe(q1), srv.subscribe(q2)
    srv.unsubscribe(s1)
    srv.submit_update(_stream(g, 1, seed=13)[0])
    srv.apply_update_tick()
    dur.close()
    _, info = recover_server(DurabilityConfig(str(tmp_path)))
    assert sorted(info["subscriptions"]) == [s2]


# ------------------------------------------------------------------ scrub ---


def test_scrub_clean_and_detects(base):
    g, entries = base
    eng = _clone(entries["grouped-stacked"])
    for u in _stream(g, 2, seed=14):
        eng.apply_updates([u])
    report = scrub_engine(eng)
    assert report["ok"] and report["partitions_checked"] == [0, 1, 2]

    eng.models[0].index.levels[0]["mbr"][0, 0, 1] -= 10  # silent bit rot
    bad = scrub_engine(eng)
    assert not bad["ok"]
    assert any(v["check"] == "mbr" for v in bad["violations"])

    eng2 = _clone(entries["path-stacked"])
    eng2.apply_updates([_stream(g, 1, seed=15)[0]])
    eng2.delta.parts[0].n_tomb += 1  # bookkeeping drift
    bad2 = scrub_engine(eng2)
    assert any(v["check"] == "tombstone" for v in bad2["violations"])


def test_server_scrub_admin_call(base, tmp_path):
    g, entries = base
    eng = _clone(entries["path-stacked"])
    srv = MatchServer(eng, MatchServeConfig())
    assert srv.scrub(sample=2)["ok"]


# ------------------------------------------------- DirExchange torn blobs ---


def test_dir_exchange_rejects_torn_blob(tmp_path):
    ex = DirExchange(tmp_path)
    ex.put("k", {"tag": 1}, {"x": np.arange(5)})
    meta, arrays = ex.get("k", timeout=1)
    assert meta == {"tag": 1} and np.array_equal(arrays["x"], np.arange(5))
    blob = tmp_path / "k.npz"
    with open(blob, "r+b") as f:
        f.truncate(blob.stat().st_size - 3)
    with pytest.raises(HostLostError):
        ex.get("k", timeout=1)
    ex.put("k2", {}, {"x": np.arange(5)})
    flip_byte(tmp_path / "k2.npz", offset=-2)
    with pytest.raises(HostLostError):
        ex.get("k2", timeout=1)


# ------------------------------------------------------- server wiring -----


def test_server_genesis_and_durable_restart(base, tmp_path):
    """A durable server on a fresh directory snapshots its build (genesis)
    so recovery works even before the first update tick."""
    g, entries = base
    eng = _clone(entries["path-stacked"])
    cfg = DurabilityConfig(str(tmp_path), snapshot_every=2)
    MatchServer(eng, MatchServeConfig(durability=cfg))
    recovered, info = recover_engine(cfg)
    assert info["epoch"] == 0 and info["replayed"] == 0
    assert _identical(recovered, eng, _queries(g))
