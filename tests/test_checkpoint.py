"""Checkpoint plane: store snapshots + WAL compaction (both backends)
and scheduler state checkpoints (save / warm restore / delta replay /
loud cold fallback).

The crash matrix the store tests pin (temp-file + rename atomicity):

- kill mid-snapshot: a torn ``.snap.tmp`` is left behind — boot must
  recover from the PREVIOUS snapshot + the full (untruncated) WAL;
- crash after the rename but before the WAL truncation: the new
  snapshot replays first, then the stale WAL re-applies a prefix of the
  history it already contains — last-write-wins records must converge
  to the exact pre-crash state;
- WAL truncation: restart replay is bounded by snapshot cadence, not
  total history.
"""

import json
import os
import shutil
import time

import pytest

from cronsun_tpu.core import Keyspace
from cronsun_tpu.store.memstore import MemStore
from cronsun_tpu.store.native import NativeStoreServer, find_binary
from cronsun_tpu.store.remote import RemoteStore


# ---------------------------------------------------------------------------
# store snapshots + WAL (Python backend: deterministic crash injection)
# ---------------------------------------------------------------------------

def _seed(s):
    r1 = s.put("/jobs/a", "v1")
    s.put("/jobs/a", "v2")
    s.put("/jobs/b", "x")
    s.delete("/jobs/b")
    lease = s.grant(30)
    s.put("/leased", "l", lease=lease)
    for i in range(50):
        s.put("/hot", f"val-{i}")
    return r1, lease


def test_memstore_snapshot_truncates_and_restores(tmp_path):
    wal = str(tmp_path / "store.wal")
    s = MemStore().open_wal(wal)
    r1, lease = _seed(s)
    assert s._wal.size() > 0
    rev = s.snapshot()
    assert rev == s.rev()
    # the WAL is truncated: replay after a restart is the snapshot +
    # the post-snapshot tail only
    assert s._wal.size() == 0
    s.put("/post", "tail")
    tail = s._wal.size()
    assert 0 < tail < 80      # exactly one record
    s.close()

    s2 = MemStore().open_wal(wal)
    assert s2.get("/jobs/a").value == "v2"
    assert s2.get("/jobs/a").create_rev == r1
    assert s2.get("/jobs/b") is None
    assert s2.get("/hot").value == "val-49"
    assert s2.get("/post").value == "tail"
    assert s2.keepalive(lease)           # lease survived with its ttl
    assert s2.rev() >= rev + 1
    ops = s2.op_stats()
    assert ops["snapshot_load"]["count"] == 1
    assert ops["wal_replay"]["count"] == 1
    s2.close()


def test_memstore_boot_recovers_from_torn_snapshot_tmp(tmp_path):
    """Kill mid-snapshot: the torn ``.snap.tmp`` must be ignored and
    boot recover from the previous snapshot + the full WAL."""
    wal = str(tmp_path / "store.wal")
    s = MemStore().open_wal(wal)
    _seed(s)
    s.close()
    # simulate a crash mid-snapshot-write: garbage temp file alongside
    # the real artifacts
    with open(wal + ".snap.tmp", "w") as f:
        f.write('["v",99999')          # torn, not even valid JSON
    s2 = MemStore().open_wal(wal)
    assert s2.get("/jobs/a").value == "v2"
    assert s2.get("/hot").value == "val-49"
    s2.close()


def test_memstore_boot_converges_after_crash_before_truncate(tmp_path):
    """Crash after the snapshot rename but before the WAL truncation:
    the stale WAL re-applies a prefix of the history the snapshot
    already contains; last-write-wins replay must converge to the
    exact pre-crash KV state."""
    wal = str(tmp_path / "store.wal")
    s = MemStore().open_wal(wal)
    _seed(s)
    # preserve the pre-snapshot WAL, snapshot (which truncates), then
    # put the old WAL back — exactly the rename-then-crash artifact set
    shutil.copy(wal, wal + ".pre")
    s.snapshot()
    # the store object keeps appending to the (now truncated) file; we
    # model the crash by abandoning it entirely
    s._wal.close()
    s._wal = None
    s.close()
    os.replace(wal + ".pre", wal)

    s2 = MemStore().open_wal(wal)
    assert s2.get("/jobs/a").value == "v2"
    assert s2.get("/jobs/b") is None
    assert s2.get("/hot").value == "val-49"
    assert s2.get("/leased") is not None
    s2.close()


def test_memstore_corrupt_wal_mid_file_refuses_boot(tmp_path):
    """A torn FINAL record is a tolerated crash artifact; a bad record
    with more records after it is corruption and must refuse to boot,
    not silently drop history."""
    from cronsun_tpu.checkpoint.walsnap import SnapshotCorrupt
    wal = str(tmp_path / "store.wal")
    s = MemStore().open_wal(wal)
    s.put("/a", "1")
    s.put("/b", "2")
    s.close()
    lines = open(wal).read().splitlines()
    assert len(lines) >= 2
    lines[0] = '["p", "torn'
    with open(wal, "w") as f:
        f.write("\n".join(lines) + "\n")
    with pytest.raises(SnapshotCorrupt):
        MemStore().open_wal(wal)
    # torn FINAL record: tolerated
    s2 = MemStore().open_wal(str(tmp_path / "w2.wal"))
    s2.put("/a", "1")
    s2.close()
    with open(str(tmp_path / "w2.wal"), "a") as f:
        f.write('["p","/x"')
    s3 = MemStore().open_wal(str(tmp_path / "w2.wal"))
    assert s3.get("/a").value == "1"
    s3.close()


def test_snapshot_drops_keys_of_vanished_leases(tmp_path):
    """A snapshot can race a revoke/expiry between the lease pop and
    the attached-key deletes: the image then carries keys with a
    dangling lease id and no lease record.  Replay must DROP them —
    keeping them would resurrect doomed keys permanently, attached to
    a lease that can never expire them (e.g. a dead node's lock key
    becoming a phantom lock)."""
    wal = str(tmp_path / "store.wal")
    s = MemStore().open_wal(wal)
    l = s.grant(30)
    s.put("/doomed", "x", lease=l)
    s.put("/keep", "y")
    # simulate the raced artifact: lease popped (its "x" truncated away
    # with the WAL), key deletes not yet run when the image was taken
    with s._lease_lock:
        del s._leases[l]
    s.snapshot()
    s.close()
    s2 = MemStore().open_wal(wal)
    assert s2.get("/doomed") is None, "revoked-lease key resurrected"
    assert s2.get("/keep").value == "y"
    s2.close()


def test_memstore_sweeper_compacts_oversized_wal(tmp_path):
    """Size-triggered compaction: the sweeper snapshots once the WAL
    exceeds the bound, keeping restart replay bounded by cadence."""
    wal = str(tmp_path / "store.wal")
    s = MemStore().open_wal(wal, compact_bytes=2048)
    s.start_sweeper(interval=0.05)
    for i in range(300):
        s.put("/hot", f"value-{i}")
    # wait for the op-stat too: the staggered snapshot rotates the WAL
    # (size drops) at the PIN but records the op only when imaging
    # finishes, so size alone races the counter
    deadline = time.time() + 5
    while time.time() < deadline and (
            s._wal.size() > 2048
            or s.op_stats()["snapshot"]["count"] < 2):
        time.sleep(0.05)
    assert s._wal.size() <= 2048, "sweeper never compacted the WAL"
    assert s.op_stats()["snapshot"]["count"] >= 2   # boot + sweeper
    s.close()
    s2 = MemStore().open_wal(wal)
    assert s2.get("/hot").value == "value-299"
    s2.close()


# ---------------------------------------------------------------------------
# staggered snapshot imaging (COW consistency + crash matrix)
# ---------------------------------------------------------------------------

def test_staggered_snapshot_is_point_in_time(tmp_path, monkeypatch):
    """Writes racing the image land in COW side buffers: the .snap must
    read as of the PIN — pre-image for mutated keys, no post-pin keys —
    while boot (snap + rotated + tail) still converges to the live
    state."""
    from cronsun_tpu.checkpoint.walsnap import read_records
    import cronsun_tpu.checkpoint.walsnap as walsnap
    wal = str(tmp_path / "s.wal")
    s = MemStore().open_wal(wal)
    s.put("/a", "old")
    s.put("/gone", "x")
    real = walsnap.write_snapshot

    def mutating(path, lines):
        # the pin has been released, no stripe imaged yet: these hit
        # the COW path exactly like a concurrent writer would
        s.put("/a", "new")
        s.delete("/gone")
        s.put("/fresh", "y")
        return real(path, lines)
    monkeypatch.setattr(walsnap, "write_snapshot", mutating)
    s.snapshot()
    monkeypatch.setattr(walsnap, "write_snapshot", real)
    snap_recs = {r[1]: r[2] for r in read_records(wal + ".snap")
                 if r[0] == "s"}
    assert snap_recs["/a"] == "old", "image leaked a post-pin write"
    assert "/gone" in snap_recs, "image leaked a post-pin delete"
    assert "/fresh" not in snap_recs, "image leaked a post-pin create"
    assert s.get("/a").value == "new"          # live state unperturbed
    assert s.op_stats()["snapshot_pin"]["count"] >= 1
    s.close()
    s2 = MemStore().open_wal(wal)
    assert s2.get("/a").value == "new"
    assert s2.get("/gone") is None
    assert s2.get("/fresh").value == "y"
    s2.close()


def test_staggered_snapshot_crash_mid_image_converges(tmp_path,
                                                      monkeypatch):
    """Crash between the stripe imaging and the COW drain (mid-image):
    artifacts are the OLD .snap, the rotated pre-pin records (FILE.1)
    and the fresh post-pin WAL.  Boot must converge to the exact
    pre-crash state from the previous snapshot + both record files, and
    a RETRY snapshot merges the parked records instead of dropping
    them."""
    import cronsun_tpu.checkpoint.walsnap as walsnap
    wal = str(tmp_path / "s.wal")
    s = MemStore().open_wal(wal)
    s.put("/a", "1")
    s.put("/b", "2")
    s.snapshot()                     # a real previous snapshot
    s.put("/a", "3")                 # pre-pin tail

    real = walsnap.write_snapshot
    cur = [s]                        # the store the crash injects into

    def dying(path, lines):
        cur[0].put("/post", "late")  # post-pin write -> fresh WAL
        raise OSError("disk died mid-image")
    monkeypatch.setattr(walsnap, "write_snapshot", dying)
    with pytest.raises(OSError):
        s.snapshot()
    monkeypatch.setattr(walsnap, "write_snapshot", real)
    assert os.path.exists(wal + ".1"), "pre-pin records not parked"
    s.put("/b", "4")                 # life goes on into the fresh WAL
    final = {"/a": "3", "/b": "4", "/post": "late"}
    s.close()

    s2 = MemStore().open_wal(wal)
    for k, v in final.items():
        assert s2.get(k).value == v, f"{k} diverged after crash replay"
    assert not os.path.exists(wal + ".1")   # boot compaction covered it
    s2.close()

    # retry path WITHOUT an intervening boot: a second snapshot merges
    # the already-parked FILE.1 with the current WAL
    s3 = MemStore().open_wal(wal)
    s3.put("/c", "5")
    cur[0] = s3
    monkeypatch.setattr(walsnap, "write_snapshot", dying)
    with pytest.raises(OSError):
        s3.snapshot()
    monkeypatch.setattr(walsnap, "write_snapshot", real)
    s3.put("/c", "6")
    s3.snapshot()                    # retry succeeds, merges FILE.1
    assert not os.path.exists(wal + ".1")
    s3.close()
    s4 = MemStore().open_wal(wal)
    assert s4.get("/c").value == "6"
    assert s4.get("/post").value == "late"
    s4.close()


def test_rotate_merge_trims_torn_tail(tmp_path, monkeypatch):
    """A parked FILE.1 whose final line is TORN (a merge that died
    mid-append): the next rotation must trim it before appending —
    gluing records onto the torn line would read as mid-file corruption
    at boot and refuse to start."""
    import cronsun_tpu.checkpoint.walsnap as walsnap
    wal = str(tmp_path / "s.wal")
    s = MemStore().open_wal(wal)
    s.put("/a", "1")
    with open(wal + ".1", "w") as f:
        f.write('["p","/old","x",0]\n["p","/torn')    # torn final line
    real = walsnap.write_snapshot

    def dying(path, lines):
        raise OSError("disk died post-rotate")
    monkeypatch.setattr(walsnap, "write_snapshot", dying)
    with pytest.raises(OSError):
        s.snapshot()          # the pin merged the live WAL into FILE.1
    monkeypatch.setattr(walsnap, "write_snapshot", real)
    s.close()
    s2 = MemStore().open_wal(wal)   # pre-fix: SnapshotCorrupt here
    assert s2.get("/a").value == "1"
    assert s2.get("/old").value == "x"
    assert s2.get("/torn") is None  # the torn record was dropped
    s2.close()


def test_snapshot_staggered_off_rollback(tmp_path):
    """The rollback switch: full-lock imaging still round-trips and
    never records a pin op."""
    wal = str(tmp_path / "s.wal")
    s = MemStore(snapshot_staggered=False).open_wal(wal)
    _seed(s)
    s.snapshot()
    assert "snapshot_pin" not in s.op_stats()
    s.put("/post", "tail")
    s.close()
    s2 = MemStore().open_wal(wal)
    assert s2.get("/jobs/a").value == "v2"
    assert s2.get("/post").value == "tail"
    s2.close()


# ---------------------------------------------------------------------------
# store snapshots + WAL (native backend, over the wire)
# ---------------------------------------------------------------------------

def _native(tmp_path, **kw):
    binary = find_binary()
    if binary is None:
        pytest.skip("native store binary unavailable")
    return NativeStoreServer(binary=binary, wal=str(tmp_path / "store.wal"),
                             **kw)


def test_native_snapshot_op_truncates_wal_and_survives_kill9(tmp_path):
    """The live snapshot op: WAL truncated to entries after the tagged
    revision; a kill -9 later restores snapshot + tail exactly —
    restart replay is bounded by snapshot cadence, not total history."""
    wal = str(tmp_path / "store.wal")
    srv = _native(tmp_path)
    s = RemoteStore(srv.host, srv.port, reconnect=False)
    r1, lease = _seed(s)
    assert os.path.getsize(wal) > 0
    rev = s.snapshot()
    assert rev == s.rev()
    assert os.path.getsize(wal) == 0          # truncated
    assert os.path.getsize(wal + ".snap") > 0
    s.put("/post", "tail")
    time.sleep(0.3)                           # sync rides the sweeper
    tail_size = os.path.getsize(wal)
    assert 0 < tail_size < 80                 # ONLY the post-snapshot op
    s.close()
    srv._proc.kill()
    srv._proc.wait()

    srv2 = _native(tmp_path)
    try:
        s2 = RemoteStore(srv2.host, srv2.port, reconnect=False)
        assert s2.get("/jobs/a").value == "v2"
        assert s2.get("/jobs/a").create_rev == r1
        assert s2.get("/jobs/b") is None
        assert s2.get("/hot").value == "val-49"
        assert s2.get("/post").value == "tail"
        assert s2.keepalive(lease)
        ops = s2.op_stats()
        assert ops["snapshot_load"]["count"] == 1
        assert ops["wal_replay"]["count"] == 1
        s2.close()
    finally:
        srv2.stop()


def test_native_boot_recovers_from_torn_snapshot_tmp(tmp_path):
    """Native mid-snapshot crash artifact: torn .snap.tmp is ignored,
    boot recovers from the previous snapshot + full WAL."""
    wal = str(tmp_path / "store.wal")
    srv = _native(tmp_path)
    s = RemoteStore(srv.host, srv.port, reconnect=False)
    _seed(s)
    s.snapshot()
    s.put("/post", "tail")
    time.sleep(0.3)
    s.close()
    srv._proc.kill()
    srv._proc.wait()
    with open(wal + ".snap.tmp", "w") as f:
        f.write('["v",42')                    # torn temp from the crash
    srv2 = _native(tmp_path)
    try:
        s2 = RemoteStore(srv2.host, srv2.port, reconnect=False)
        assert s2.get("/jobs/a").value == "v2"
        assert s2.get("/hot").value == "val-49"
        assert s2.get("/post").value == "tail"
        s2.close()
    finally:
        srv2.stop()


def test_native_staggered_crash_artifacts_converge(tmp_path):
    """Native mid-image crash artifact set: a parked FILE.1 (pre-pin
    records) beside the live WAL (post-pin records).  Boot must replay
    snap -> FILE.1 -> WAL in that order (last-write-wins converges to
    the pre-crash state) and the boot compaction must retire FILE.1."""
    wal = str(tmp_path / "store.wal")
    srv = _native(tmp_path)
    s = RemoteStore(srv.host, srv.port, reconnect=False)
    s.put("/only1", "a")
    s.put("/k", "v1")
    time.sleep(0.3)                   # sync rides the sweeper
    s.close()
    srv._proc.kill()
    srv._proc.wait()
    # craft the mid-image artifact set: every record so far parked in
    # FILE.1, one post-pin mutation in the (fresh) WAL
    os.replace(wal, wal + ".1")
    with open(wal, "w") as f:
        f.write('["p","/k","v2",0]\n')
    srv2 = _native(tmp_path)
    try:
        s2 = RemoteStore(srv2.host, srv2.port, reconnect=False)
        assert s2.get("/only1").value == "a"    # FILE.1 replayed
        assert s2.get("/k").value == "v2"       # WAL wins over FILE.1
        assert not os.path.exists(wal + ".1")   # boot compaction
        # the live staggered op records its pin beside the image
        s2.put("/more", "x")
        s2.snapshot()
        ops = s2.op_stats()
        assert ops["snapshot_pin"]["count"] >= 1
        s2.close()
    finally:
        srv2.stop()


def test_native_compaction_loop_bounds_wal(tmp_path):
    """--compact-wal-bytes: the server snapshots by itself once the WAL
    exceeds the bound."""
    wal = str(tmp_path / "store.wal")
    srv = _native(tmp_path, compact_wal_bytes=2048)
    try:
        s = RemoteStore(srv.host, srv.port, reconnect=False)
        for i in range(300):
            s.put("/hot", f"value-{i}")
        deadline = time.time() + 5
        while time.time() < deadline and os.path.getsize(wal) > 2048:
            time.sleep(0.05)
        assert os.path.getsize(wal) <= 2048, \
            "server never compacted the WAL"
        assert s.op_stats()["snapshot"]["count"] >= 1
        s.close()
    finally:
        srv.stop()


def test_snapshot_refused_without_wal():
    """Both surfaces refuse a snapshot with no WAL configured (loud
    error, not a silent no-op)."""
    from cronsun_tpu.store.remote import RemoteStoreError, StoreServer
    s = MemStore()
    with pytest.raises(RuntimeError):
        s.snapshot()
    srv = StoreServer().start()
    c = RemoteStore(srv.host, srv.port, reconnect=False)
    with pytest.raises(RemoteStoreError):
        c.snapshot()
    assert c.rev() >= 0
    c.close()
    srv.stop()


# ---------------------------------------------------------------------------
# scheduler checkpoints
# ---------------------------------------------------------------------------

def _seed_sched(store, ks, n_jobs=64, n_nodes=8):
    for i in range(n_nodes):
        store.put(ks.node_key(f"n{i}"), "1")
    store.put(ks.group_key("g0"), json.dumps(
        {"id": "g0", "name": "g0",
         "nids": [f"n{i}" for i in range(max(1, n_nodes // 2))]}))
    for i in range(n_jobs):
        kind = [0, 2, 1][i % 3]
        rule = {"id": "r", "timer": f"@every {10 + i % 50}s"}
        if i % 4:
            rule["nids"] = [f"n{i % n_nodes}"]
        else:
            rule["gids"] = ["g0"]
        store.put(f"{ks.cmd}g/j{i}", json.dumps(
            {"name": f"j{i}", "command": "true", "kind": kind,
             "rules": [rule]}))


def _make_sched(store, ks, node_id, **kw):
    from cronsun_tpu.sched import SchedulerService
    return SchedulerService(store, ks=ks, job_capacity=512,
                            node_capacity=32, node_id=node_id, **kw)


def _window_orders(svc, ep, window=2):
    """Plan a fixed window and build its orders — the dispatch plan a
    leader would publish, without leading."""
    secs, acct = [], []
    n = 0
    for p in svc.planner.plan_window(ep, window):
        n += svc._build_plan_orders(p, secs, acct)
    return n, sorted((e, k, v) for e, orders in secs for k, v in orders)


@pytest.fixture
def sched_world(tmp_path):
    ks = Keyspace()
    store = MemStore()
    _seed_sched(store, ks)
    svcs = []
    yield store, ks, str(tmp_path), svcs
    for s in svcs:
        s.stop()


def _fire_set(ks, orders):
    """Placement-independent view of a built window: broadcast orders
    byte-for-byte, exclusive fires as the multiset of (epoch, job)
    bundle entries (WHICH node a group-placed job lands on legitimately
    depends on row-allocation order, which a fresh cold load permutes)."""
    bcast, excl = [], []
    for ep, key, val in orders:
        if key.startswith(ks.dispatch_all):
            bcast.append((ep, key, val))
        else:
            excl += [(ep, e) for e in json.loads(val)]
    return sorted(bcast), sorted(excl)


def test_sched_checkpoint_roundtrip_identical_dispatch(sched_world):
    """The restore contract, both halves: (1) a restored standby that
    replayed the delta is BIT-IDENTICAL to the live scheduler it
    checkpointed — same row allocation, same mirrors, byte-identical
    dispatch orders for the next window; (2) against a fresh cold load
    of the current store it fires the exact same (epoch, job) set
    (placement of group-placed jobs may permute with row order).  The
    delta replayed between checkpoint and takeover covers a job added,
    a job deleted, a node added, a proc mirror entry and an Alone lock
    (which no mirror holds: the node judges it)."""
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A")
    svcs.append(a)
    out = a.checkpoint_save(path=os.path.join(d, "sched.ckpt"))
    assert out["rev"] > 0

    # the delta between checkpoint and takeover
    store.put(f"{ks.cmd}g/extra", json.dumps(
        {"name": "extra", "command": "true", "kind": 2,
         "rules": [{"id": "r", "timer": "@every 10s", "nids": ["n1"]}]}))
    store.delete(f"{ks.cmd}g/j5")
    store.put(ks.node_key("n8"), "1")
    lease = store.grant(60)
    store.put(ks.proc_key("n1", "g", "j1", 1234), "x", lease=lease)
    store.put(ks.alone_lock_key("j2"), "n0", lease=lease)

    b = _make_sched(store, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    assert b.checkpoint_restored
    b.drain_watches()                 # apply the replayed delta
    b._flush_device()
    # A is live on the same store: apply the SAME delta to it — B
    # restored A's allocator state and replays the same sequence, so
    # the two must now be byte-identical
    a.drain_watches()
    a._flush_device()

    assert b.jobs.keys() == a.jobs.keys()
    assert ("g", "extra") in b.jobs and ("g", "j5") not in b.jobs
    assert b.universe.index == a.universe.index
    assert b.rows.by_cmd == a.rows.by_cmd
    assert b._procs == a._procs
    assert b._excl_cnt == a._excl_cnt

    ep = (int(time.time()) // 60 + 2) * 60
    nb, ob = _window_orders(b, ep)
    na, oa = _window_orders(a, ep)
    assert nb == na
    assert ob == oa                   # byte-identical orders
    assert len(ob) > 0                # the window actually dispatches

    # half (2): a fresh cold load fires the same (epoch, job) set
    c = _make_sched(store, ks, "C")
    svcs.append(c)
    assert b.jobs.keys() == c.jobs.keys()
    assert b._procs == c._procs
    nc, oc = _window_orders(c, ep)
    assert nb == nc
    assert _fire_set(ks, ob) == _fire_set(ks, oc)


def test_sched_checkpoint_restore_is_warm_on_metrics(sched_world):
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A")
    svcs.append(a)
    a.checkpoint_save(path=os.path.join(d, "sched.ckpt"))
    snap = a.metrics_snapshot()
    assert snap["checkpoint_saves_total"] == 1
    assert snap["checkpoint_last_rev"] > 0
    assert snap["checkpoint_restored"] == 0

    b = _make_sched(store, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    snap = b.metrics_snapshot()
    assert snap["checkpoint_restored"] == 1
    assert snap["checkpoint_restore_ms"] > 0


def test_sched_checkpoint_too_stale_falls_back_cold(sched_world):
    """A checkpoint whose revision fell out of the store's bounded
    watch history must cold-load (loudly), never restore a state whose
    delta is unreplayable."""
    ks = Keyspace()
    store = MemStore(history=64)
    _seed_sched(store, ks)
    _, _, d, svcs = sched_world
    a = _make_sched(store, ks, "A")
    svcs.append(a)
    a.checkpoint_save(path=os.path.join(d, "sched.ckpt"))
    for i in range(500):              # blow past the 64-event ring
        store.put("/junk", str(i))
    b = _make_sched(store, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    assert not b.checkpoint_restored
    assert len(b.jobs) == 64          # cold load still produced a leader


def test_sched_checkpoint_shape_mismatch_falls_back_cold(sched_world):
    from cronsun_tpu.sched import SchedulerService
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A")
    svcs.append(a)
    a.checkpoint_save(path=os.path.join(d, "sched.ckpt"))
    b = SchedulerService(store, ks=ks, job_capacity=1024,
                         node_capacity=32, node_id="B",
                         checkpoint_dir=d)
    svcs.append(b)
    assert not b.checkpoint_restored
    assert len(b.jobs) == 64


def test_sched_checkpoint_rev_regressed_store_falls_back_cold(sched_world):
    """A store whose revision is BEHIND the checkpoint's rev is a
    DIFFERENT incarnation (wiped/lost WAL): past-the-end watches
    register silently, so without the explicit rev guard the scheduler
    would boot warm against ghost state and never resync."""
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A")
    svcs.append(a)
    a.checkpoint_save(path=os.path.join(d, "sched.ckpt"))
    fresh = MemStore()              # the "restarted without WAL" store
    _seed_sched(fresh, ks, n_jobs=8)
    assert fresh.rev() < a.metrics_snapshot()["checkpoint_last_rev"]
    b = _make_sched(fresh, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    assert not b.checkpoint_restored
    assert len(b.jobs) == 8         # cold load of the REAL store


def test_sched_checkpoint_refused_on_non_plain_planner(sched_world, capsys):
    """checkpoint_dir with a sharded/proxied planner must be refused at
    construction (not just in the launcher): restoring single-device
    arrays onto a mesh planner would break its sharding invariants."""
    from cronsun_tpu.ops.planner import TickPlanner

    class NotPlain(TickPlanner):
        pass

    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A", checkpoint_dir=d,
                    planner=NotPlain(job_capacity=512, node_capacity=32))
    svcs.append(a)
    assert a.checkpoint_dir is None
    store.put(ks.ckpt_req, "1")
    a.step()                        # request must be a no-op, not a save
    assert not os.path.exists(os.path.join(d, "sched.ckpt"))


def test_sched_checkpoint_missing_or_torn_falls_back_cold(sched_world):
    store, ks, d, svcs = sched_world
    b = _make_sched(store, ks, "B", checkpoint_dir=d)   # no file at all
    svcs.append(b)
    assert not b.checkpoint_restored
    assert len(b.jobs) == 64
    with open(os.path.join(d, "sched.ckpt"), "wb") as f:
        f.write(b"\x80\x04 torn pickle")
    c = _make_sched(store, ks, "C", checkpoint_dir=d)
    svcs.append(c)
    assert not c.checkpoint_restored
    assert len(c.jobs) == 64


def test_sched_checkpoint_missing_field_falls_back_cold(sched_world):
    """A version-valid checkpoint missing an expected field (foreign
    build, hand-edited file) must cold-load LOUDLY — never crash-loop
    the constructor on a KeyError with the bad file still on disk."""
    import pickle
    from cronsun_tpu.checkpoint.sched_ckpt import FORMAT_VERSION
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A")
    svcs.append(a)
    a.checkpoint_save(path=os.path.join(d, "sched.ckpt"))
    st = pickle.load(open(os.path.join(d, "sched.ckpt"), "rb"))
    assert st["version"] == FORMAT_VERSION
    del st["mirrors"]
    st["rows"].pop("by_cmd")
    with open(os.path.join(d, "sched.ckpt"), "wb") as f:
        pickle.dump(st, f)
    b = _make_sched(store, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    assert not b.checkpoint_restored
    assert len(b.jobs) == 64


def test_sched_checkpoint_of_the_lock_mirroring_parent_restores(sched_world):
    """A scheduler that mirrored the KindAlone locks wrote them into its
    checkpoints: ``mirrors["alone"]`` in the base, ``alone`` events in
    the delta chain.  Both still restore warm here — the key and the
    events are ignored — and the restored scheduler plans the same
    window as the live one."""
    import pickle
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A", checkpoint_dir=d)
    svcs.append(a)
    assert a.checkpoint_save()["kind"] == "full"
    _mutate_store(store, ks)
    a.drain_watches()
    assert a.checkpoint_save()["kind"] == "delta"
    base, delta = (os.path.join(d, f) for f in ("sched.ckpt",
                                                "sched.ckpt.d1"))
    st = pickle.load(open(base, "rb"))
    assert sorted(st["mirrors"]) == ["excl", "load", "orders", "procs"]
    st["mirrors"]["alone"] = {"j2"}
    st["rd"]["flags"][st["rows"]["by_cmd"][("g", "j2", "r")]] |= 4  # its
    # row flag for an Alone job
    rec = pickle.load(open(delta, "rb"))
    assert not any(ev[0] == "alone" for ev in rec["events"])
    rec["events"] += [("alone", "PUT", ks.alone_lock_key("j2"), "n0"),
                      ("alone", "DELETE", ks.alone_lock_key("j2"), "")]
    for path, obj in ((base, st), (delta, rec)):
        with open(path, "wb") as f:
            pickle.dump(obj, f)
    b = _make_sched(store, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    assert b.checkpoint_restored
    b.drain_watches()
    b._flush_device()
    a._flush_device()
    assert b.rows.by_cmd == a.rows.by_cmd and b._procs == a._procs
    ep = (int(time.time()) // 60 + 2) * 60
    assert _window_orders(b, ep) == _window_orders(a, ep)
    assert _window_orders(b, ep)[0] > 0


def test_sched_checkpoint_request_key_triggers_save(sched_world):
    """The operator trigger: a PUT on the ckpt request key (what the
    web /v1/checkpoint endpoint writes) makes the scheduler save and
    ack under ckpt/done/<node_id>."""
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A", checkpoint_dir=d)
    svcs.append(a)
    store.put(ks.ckpt_req, "42")
    a.step()                          # drain + _maybe_checkpoint
    assert os.path.exists(os.path.join(d, "sched.ckpt"))
    done = store.get(ks.ckpt_done_key("A"))
    assert done is not None
    ack = json.loads(done.value)
    assert ack["rev"] > 0
    assert a.metrics_snapshot()["checkpoint_saves_total"] == 1


def test_sched_periodic_checkpoint(sched_world):
    store, ks, d, svcs = sched_world
    clock = [1000.0]
    a = _make_sched(store, ks, "A", checkpoint_dir=d,
                    checkpoint_interval_s=30.0,
                    clock=lambda: clock[0])
    svcs.append(a)
    a.step()
    assert not os.path.exists(os.path.join(d, "sched.ckpt"))
    clock[0] += 31.0
    a.step()
    # periodic full saves serialize on the background writer (the step
    # thread only pays barrier + capture): join it before asserting
    a._ckpt_join()
    assert os.path.exists(os.path.join(d, "sched.ckpt"))
    assert a.metrics_snapshot()["checkpoint_saves_total"] == 1
    assert a.metrics_snapshot()["checkpoint_bg_writes_total"] == 1


# ---------------------------------------------------------------------------
# delta checkpoint chain (incremental saves; crash matrix)
# ---------------------------------------------------------------------------

def _mutate_store(store, ks, tag="extra"):
    """A small representative delta: job add, job delete, node add, a
    proc mirror entry, and an Alone lock (mirrored nowhere)."""
    store.put(f"{ks.cmd}g/{tag}", json.dumps(
        {"name": tag, "command": "true", "kind": 2,
         "rules": [{"id": "r", "timer": "@every 10s", "nids": ["n1"]}]}))
    store.delete(f"{ks.cmd}g/j5")
    store.put(ks.node_key("n8"), "1")
    lease = store.grant(60)
    store.put(ks.proc_key("n1", "g", "j1", 1234), "x", lease=lease)
    store.put(ks.alone_lock_key("j2"), "n0", lease=lease)


def test_delta_checkpoint_roundtrip_identical(sched_world):
    """Base + delta chain restores BIT-IDENTICAL to the live scheduler:
    full save, sparse mutations, DELTA save (small file), restore folds
    the chain — same rows/mirrors, byte-identical window orders, and
    the restored instance can EXTEND the chain (seq continues)."""
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A", checkpoint_dir=d)
    svcs.append(a)
    out = a.checkpoint_save()
    assert out["kind"] == "full"
    _mutate_store(store, ks)
    a.drain_watches()
    out2 = a.checkpoint_save()
    assert out2["kind"] == "delta"
    assert os.path.exists(os.path.join(d, "sched.ckpt.d1"))

    b = _make_sched(store, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    assert b.checkpoint_restored
    b.drain_watches()
    b._flush_device()
    a.drain_watches()
    a._flush_device()
    assert b.jobs.keys() == a.jobs.keys()
    assert ("g", "extra") in b.jobs and ("g", "j5") not in b.jobs
    assert b.rows.by_cmd == a.rows.by_cmd
    assert b._procs == a._procs
    assert b._excl_cnt == a._excl_cnt
    ep = (int(time.time()) // 60 + 2) * 60
    assert _window_orders(b, ep) == _window_orders(a, ep)
    assert _window_orders(b, ep)[0] > 0

    # chain continuation: B's next save extends the restored chain
    _mutate_store(store, ks, tag="extra2")
    b.drain_watches()
    out3 = b.checkpoint_save()
    assert out3["kind"] == "delta"
    assert os.path.exists(os.path.join(d, "sched.ckpt.d2"))
    c = _make_sched(store, ks, "C", checkpoint_dir=d)
    svcs.append(c)
    assert c.checkpoint_restored
    assert ("g", "extra2") in c.jobs


def test_delta_records_own_publish_accounting(sched_world):
    """The leader's own-publish order reservations never echo back
    through the delete-only orders watch; the delta stream records them
    at accounting time (the synthetic ``ordmirror`` stream) so a
    restored standby's mirrors match the live leader's without waiting
    on anti-entropy."""
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A", checkpoint_dir=d)
    svcs.append(a)
    a.checkpoint_save()
    key = f"{ks.dispatch}n1/12345"
    a._acct_add_order(key, "n1", [("g", "j1"), ("g", "j2")])
    a.checkpoint_save(kind="delta")
    b = _make_sched(store, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    assert b.checkpoint_restored
    assert b._orders == a._orders
    assert b._excl_cnt == a._excl_cnt
    assert b._load_sum == a._load_sum


def test_delta_save_roundtrips_byte_identical_to_full(sched_world):
    """The tier-1 equivalence smoke: restoring base+delta must yield the
    EXACT state a fresh FULL save at the same point restores — same
    serialized image (volatile header fields aside), same orders."""
    import numpy as np
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A", checkpoint_dir=d)
    svcs.append(a)
    a.checkpoint_save()
    _mutate_store(store, ks)
    a.drain_watches()
    a.checkpoint_save(kind="delta")
    # a SECOND, independent full save of the same live state
    full_dir = os.path.join(d, "full")
    a.checkpoint_save(path=os.path.join(full_dir, "sched.ckpt"),
                      kind="full")

    b = _make_sched(store, ks, "B", checkpoint_dir=d)          # chain
    svcs.append(b)
    c = _make_sched(store, ks, "C", checkpoint_dir=full_dir)   # full
    svcs.append(c)
    assert b.checkpoint_restored and c.checkpoint_restored
    sb = b._checkpoint_state(0)
    sc = c._checkpoint_state(0)
    for k in ("jobs", "groups", "node_caps", "rows", "universe",
              "row_phase", "row_dispatch", "col_node", "mirrors"):
        assert sb[k] == sc[k], f"state field {k} diverged"
    for k in ("elig", "exclusive", "cost"):
        assert np.array_equal(sb[k], sc[k]), f"device field {k} diverged"
    for name, arr in sb["table"].items():
        assert np.array_equal(arr, sc["table"][name]), \
            f"table field {name} diverged"
    ep = (int(time.time()) // 60 + 2) * 60
    assert _window_orders(b, ep) == _window_orders(c, ep)


def test_delta_torn_mid_chain_falls_back_cold(sched_world):
    """Torn pickle in the MIDDLE of the chain: the whole restore is
    refused (cold load) — never a fold of the valid prefix plus a
    silently dropped suffix."""
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A", checkpoint_dir=d)
    svcs.append(a)
    a.checkpoint_save()
    for tag in ("x1", "x2"):
        _mutate_store(store, ks, tag=tag)
        a.drain_watches()
        a.checkpoint_save(kind="delta")
    with open(os.path.join(d, "sched.ckpt.d1"), "wb") as f:
        f.write(b"\x80\x04 torn delta")
    b = _make_sched(store, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    assert not b.checkpoint_restored
    assert len(b.jobs) == 65          # cold load of the CURRENT store


def test_delta_missing_element_falls_back_cold(sched_world):
    """Base present but a chain element missing (seq gap): cold load."""
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A", checkpoint_dir=d)
    svcs.append(a)
    a.checkpoint_save()
    for tag in ("x1", "x2"):
        _mutate_store(store, ks, tag=tag)
        a.drain_watches()
        a.checkpoint_save(kind="delta")
    os.remove(os.path.join(d, "sched.ckpt.d1"))
    b = _make_sched(store, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    assert not b.checkpoint_restored
    assert len(b.jobs) == 65


def test_delta_foreign_chain_falls_back_cold(sched_world):
    """A delta whose nonce doesn't match the base (files moved between
    deployments) refuses the restore — cold load, loudly."""
    import pickle
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A", checkpoint_dir=d)
    svcs.append(a)
    a.checkpoint_save()
    _mutate_store(store, ks)
    a.drain_watches()
    a.checkpoint_save(kind="delta")
    p = os.path.join(d, "sched.ckpt.d1")
    rec = pickle.load(open(p, "rb"))
    rec["chain"] = "some-other-base"
    pickle.dump(rec, open(p, "wb"))
    b = _make_sched(store, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    assert not b.checkpoint_restored
    assert len(b.jobs) == 64          # 64 seeded + extra - j5


def test_full_save_rebases_and_clears_chain(sched_world):
    """A full save (auto-rebase) unlinks the stale chain elements, so a
    later restore folds nothing stale; the rebase knobs force it."""
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A", checkpoint_dir=d,
                    delta_max_chain=2)
    svcs.append(a)
    a.checkpoint_save()
    for tag in ("x1", "x2"):
        _mutate_store(store, ks, tag=tag)
        a.drain_watches()
        assert a.checkpoint_save()["kind"] == "delta"
    # chain is at the knob: the next auto save must REBASE
    _mutate_store(store, ks, tag="x3")
    a.drain_watches()
    out = a.checkpoint_save()
    assert out["kind"] == "full"
    assert not os.path.exists(os.path.join(d, "sched.ckpt.d1"))
    b = _make_sched(store, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    assert b.checkpoint_restored
    assert ("g", "x3") in b.jobs


def test_delta_buffer_invalidated_by_watch_loss(sched_world):
    """After a watch loss (resync) the recorded stream is incomplete:
    the next save must be a FULL rebase, never a delta missing the
    gap's events."""
    store, ks, d, svcs = sched_world
    a = _make_sched(store, ks, "A", checkpoint_dir=d)
    svcs.append(a)
    a.checkpoint_save()
    _mutate_store(store, ks)
    a.resync()                        # the watch-loss recovery path
    out = a.checkpoint_save()
    assert out["kind"] == "full"
    # and the rebase re-arms delta recording
    _mutate_store(store, ks, tag="post")
    a.drain_watches()
    assert a.checkpoint_save()["kind"] == "delta"


# ---------------------------------------------------------------------------
# sharded-store checkpoints (rev-vector barrier)
# ---------------------------------------------------------------------------

def _sharded_world(nshards=2):
    from cronsun_tpu.store.sharded import ShardedStore
    ks = Keyspace()
    store = ShardedStore([MemStore() for _ in range(nshards)])
    _seed_sched(store, ks)
    return store, ks


def test_sharded_store_checkpoint_not_refused(tmp_path):
    """The PR 6 refusal is GONE: checkpoint_dir against a 2-shard store
    saves (rev VECTOR) and a standby restores warm, replaying each
    shard's watch tail from its own rev+1."""
    store, ks = _sharded_world()
    d = str(tmp_path)
    svcs = []
    try:
        a = _make_sched(store, ks, "A", checkpoint_dir=d)
        svcs.append(a)
        assert a.checkpoint_dir == d       # not silently disabled
        out = a.checkpoint_save()
        assert isinstance(out["rev"], list) and len(out["rev"]) == 2
        _mutate_store(store, ks)
        a.drain_watches()
        assert a.checkpoint_save()["kind"] == "delta"

        b = _make_sched(store, ks, "B", checkpoint_dir=d)
        svcs.append(b)
        assert b.checkpoint_restored
        b.drain_watches()
        b._flush_device()
        a.drain_watches()
        a._flush_device()
        assert b.jobs.keys() == a.jobs.keys()
        assert b.rows.by_cmd == a.rows.by_cmd
        assert b._procs == a._procs
        ep = (int(time.time()) // 60 + 2) * 60
        assert _window_orders(b, ep) == _window_orders(a, ep)
        assert _window_orders(b, ep)[0] > 0
    finally:
        for s in svcs:
            s.stop()
        store.close()


def test_sharded_checkpoint_rev_vector_shape_mismatch_cold(tmp_path):
    """A checkpoint cut against N shards refuses restore against M != N
    (or an unsharded store): the revision vector is meaningless under a
    different topology — cold load, loudly."""
    store2, ks = _sharded_world(2)
    d = str(tmp_path)
    svcs = []
    try:
        a = _make_sched(store2, ks, "A", checkpoint_dir=d)
        svcs.append(a)
        a.checkpoint_save()

        from cronsun_tpu.store.sharded import ShardedStore
        store3 = ShardedStore([MemStore() for _ in range(3)],
                              verify_map=False)
        _seed_sched(store3, ks, n_jobs=8)
        b = _make_sched(store3, ks, "B", checkpoint_dir=d)
        svcs.append(b)
        assert not b.checkpoint_restored
        assert len(b.jobs) == 8

        plain = MemStore()
        _seed_sched(plain, ks, n_jobs=8)
        c = _make_sched(plain, ks, "C", checkpoint_dir=d)
        svcs.append(c)
        assert not c.checkpoint_restored
        assert len(c.jobs) == 8

        # and the reverse: a SCALAR checkpoint against a sharded store
        d2 = os.path.join(d, "scalar")
        p = _make_sched(plain, ks, "P")
        svcs.append(p)
        p.checkpoint_save(path=os.path.join(d2, "sched.ckpt"))
        q = _make_sched(store2, ks, "Q", checkpoint_dir=d2)
        svcs.append(q)
        assert not q.checkpoint_restored
    finally:
        for s in svcs:
            s.stop()
        store2.close()


# ---------------------------------------------------------------------------
# mesh-planner checkpoints (per-rank shards host-gathered through _fetch)
# ---------------------------------------------------------------------------

def _mesh_planner(kind="1d", job_capacity=2048, node_capacity=64):
    """Planners engineered to SHARE J/N across topologies (J=2048,
    N=64 for all three kinds) so a cross-topology restore exercises the
    mesh-topology check, not the earlier shape check."""
    from cronsun_tpu.parallel.mesh import (Sharded2DTickPlanner,
                                           ShardedTickPlanner, make_mesh,
                                           make_mesh2d)
    if kind == "2d":
        return Sharded2DTickPlanner(
            make_mesh2d(4, 2), job_capacity=job_capacity,
            node_capacity=node_capacity)
    return ShardedTickPlanner(
        make_mesh(8), job_capacity=job_capacity,
        node_capacity=node_capacity, impl="jnp")


def _make_mesh_sched(store, ks, node_id, kind="1d", **kw):
    from cronsun_tpu.sched import SchedulerService
    return SchedulerService(store, ks=ks, job_capacity=2048,
                            node_capacity=64, node_id=node_id,
                            planner=_mesh_planner(kind), **kw)


def test_mesh_sched_checkpoint_roundtrip(sched_world):
    """A mesh planner's scheduler ACCEPTS checkpoint_dir; a same-topology
    restore is warm and fire-set-identical (byte-identical orders: the
    restored standby replays the same allocator state and the sharded
    plan is deterministic per mesh shape)."""
    store, ks, d, svcs = sched_world
    a = _make_mesh_sched(store, ks, "A", checkpoint_dir=d)
    svcs.append(a)
    assert a.checkpoint_dir == d      # accepted, not silently disabled
    out = a.checkpoint_save()
    assert out["rev"] > 0

    # delta between checkpoint and takeover replays on restore
    store.put(f"{ks.cmd}g/extra", json.dumps(
        {"name": "extra", "command": "true", "kind": 2,
         "rules": [{"id": "r", "timer": "@every 10s", "nids": ["n1"]}]}))
    store.delete(f"{ks.cmd}g/j5")

    b = _make_mesh_sched(store, ks, "B", checkpoint_dir=d)
    svcs.append(b)
    assert b.checkpoint_restored
    b.drain_watches()
    b._flush_device()
    a.drain_watches()
    a._flush_device()
    assert b.jobs.keys() == a.jobs.keys()
    assert ("g", "extra") in b.jobs and ("g", "j5") not in b.jobs

    ep = (int(time.time()) // 60 + 2) * 60
    na, oa = _window_orders(a, ep)
    nb, ob = _window_orders(b, ep)
    assert nb == na and ob == oa and len(ob) > 0


def test_mesh_checkpoint_topology_mismatch_cold(sched_world, caplog):
    """A checkpoint cut on one mesh topology must cold-load LOUDLY on a
    different one — same J/N by construction, so only the topology tag
    can refuse it: 1-D(8) -> 2-D(4x2), and 1-D(8) -> plain planner."""
    from cronsun_tpu.sched import SchedulerService
    store, ks, d, svcs = sched_world
    a = _make_mesh_sched(store, ks, "A", checkpoint_dir=d)
    svcs.append(a)
    a.checkpoint_save()

    b = _make_mesh_sched(store, ks, "B", kind="2d", checkpoint_dir=d)
    svcs.append(b)
    assert not b.checkpoint_restored      # cold, not crashed
    assert len(b.jobs) == 64
    # shapes really did match — the topology check is what refused it
    assert b.planner.J == a.planner.J and b.planner.N == a.planner.N

    p = SchedulerService(store, ks=ks, job_capacity=2048,
                         node_capacity=64, node_id="P", checkpoint_dir=d)
    svcs.append(p)
    assert p.planner.J == a.planner.J and p.planner.N == a.planner.N
    assert not p.checkpoint_restored
    assert len(p.jobs) == 64

    # and the reverse: a PLAIN checkpoint refuses onto a mesh planner
    p.checkpoint_save()
    c = _make_mesh_sched(store, ks, "C", checkpoint_dir=d)
    svcs.append(c)
    assert not c.checkpoint_restored
    assert len(c.jobs) == 64


def test_mesh_checkpoint_refused_multiprocess_and_proxy(sched_world):
    """Multi-host mesh planners (and the hostsync proxy wrapping them)
    stay refused: restore-time coordination across ranks isn't built."""
    store, ks, d, svcs = sched_world
    mp = _mesh_planner()
    mp._multiprocess = True               # what jax.distributed would set
    from cronsun_tpu.sched import SchedulerService
    a = SchedulerService(store, ks=ks, job_capacity=2048,
                         node_capacity=64, node_id="A", planner=mp,
                         checkpoint_dir=d)
    svcs.append(a)
    assert a.checkpoint_dir is None

    from cronsun_tpu.parallel.hostsync import PlannerSyncProxy
    prox = PlannerSyncProxy(_mesh_planner())
    b = SchedulerService(store, ks=ks, job_capacity=2048,
                         node_capacity=64, node_id="B", planner=prox,
                         checkpoint_dir=d)
    svcs.append(b)
    assert b.checkpoint_dir is None
