"""Shard-routing conformance: the ShardedStore client over N store
shards must keep every contract the single store defines — and the
routing itself must be deterministic, co-locating, and identical in
every process that holds a client (store/sharded.py).

Four claim families are covered: single-key routing, split
put_many/claim_bundle_many with cross-shard exclusivity, the merged
watch stream's revision-vector resume with one lossy shard, and
py<->native parity at 1/2/4 shards over the wire.
"""

import json
import threading
import time

import pytest

from cronsun_tpu.core import Keyspace
from cronsun_tpu.store import MemStore, WatchLost
from cronsun_tpu.store.native import NativeStoreServer, find_binary
from cronsun_tpu.store.remote import RemoteStore, StoreServer
from cronsun_tpu.store.sharded import (HASH_SCHEME, ShardedStore,
                                       connect_sharded, fnv1a,
                                       prefix_shard_token, shard_index,
                                       shard_token, verify_single_store)

ks = Keyspace()


# ---------------------------------------------------------------- routing

def test_fnv1a_known_vectors():
    # standard 64-bit FNV-1a vectors — every process routes by these
    # (Python's own hash() is salted per process)
    assert fnv1a("") == 0xcbf29ce484222325
    assert fnv1a("a") == 0xaf63dc4c8601ec8c
    assert fnv1a("foobar") == 0x85944171f73967e8


def test_token_colocates_job_family():
    """A fire's whole key family — job doc, (job, second) fence, proc
    registration, run-now trigger, phase anchor, alone lock — shares
    one routing token, so the per-item claim stays single-shard."""
    tok = shard_token(ks.job_key("g1", "jobA"))
    assert tok == "j:jobA"
    assert shard_token(ks.lock_key("jobA", 1234)) == tok
    assert shard_token(ks.proc_key("n9", "g1", "jobA", 77)) == tok
    assert shard_token(ks.once_key("g1", "jobA")) == tok
    assert shard_token(ks.phase_key("g1", "jobA", "r0")) == tok
    assert shard_token(ks.alone_lock_key("jobA")) == tok


def test_token_colocates_node_family():
    tok = shard_token(ks.node_key("node-7"))
    assert tok == "n:node-7"
    assert shard_token(ks.dispatch_bundle_key("node-7", 99)) == tok
    assert shard_token(
        ks.dispatch_key("node-7", 99, "g", "j")) == tok


def test_token_default_is_full_key():
    # keys outside the family map (and outside the prefix) route by
    # full text — deterministic, never an error
    assert shard_token("/cronsun/hwm") == "/cronsun/hwm"
    assert shard_token("/other/x") == "/other/x"


def test_shardmap_pinned_to_shard_zero():
    for n in (2, 3, 8):
        assert shard_index(ks.shardmap, n) == 0


def test_single_key_routing_lands_on_one_shard():
    shards = [MemStore() for _ in range(4)]
    ss = ShardedStore(shards)
    keys = [ks.job_key("g", f"j{i}") for i in range(32)]
    for k in keys:
        ss.put(k, "doc")
    for k in keys:
        want = shard_index(k, 4)
        for i, m in enumerate(shards):
            assert (m.get(k) is not None) == (i == want), (k, i, want)
    # and gets route back through the same shard
    assert all(ss.get(k).value == "doc" for k in keys)
    ss.close()


# ---------------------------------------------------------------- splits

def test_put_many_get_many_positions_preserved():
    ss = ShardedStore([MemStore() for _ in range(3)])
    items = [(ks.job_key("g", f"j{i}"), f"v{i}") for i in range(50)]
    ss.put_many(items)
    got = ss.get_many([k for k, _ in items] + ["/cronsun/cmd/g/nope"])
    assert [kv.value for kv in got[:-1]] == [v for _, v in items]
    assert got[-1] is None
    assert ss.count_prefix(ks.cmd) == 50
    # merged prefix scan is sorted despite arbitrary shard placement
    scan = ss.get_prefix(ks.cmd)
    assert [kv.key for kv in scan] == sorted(k for k, _ in items)
    assert ss.delete_many([k for k, _ in items]) == 50
    assert ss.count_prefix(ks.cmd) == 0
    ss.close()


def test_claim_bundle_splits_and_consumes_reservation_last():
    """A coalesced (node, second) bundle whose items hash to different
    shards: every fence is claimed on ITS shard, the bundle order key
    is consumed exactly once, and winners' proc keys ride the claim."""
    shards = [MemStore() for _ in range(4)]
    ss = ShardedStore(shards)
    order_key = ks.dispatch_bundle_key("nodeX", 1000)
    jobs = [f"bj{i}" for i in range(16)]
    ss.put(order_key, json.dumps([f"g/{j}" for j in jobs]))
    items = [(ks.lock_key(j, 1000), "nodeX",
              ks.proc_key("nodeX", "g", j, 1), "pv") for j in jobs]
    # the items really do span shards (the whole point of the split)
    assert len({shard_index(it[0], 4) for it in items}) > 1
    lease = ss.grant(30.0)
    wins = ss.claim_bundle(order_key, items, lease, lease)
    assert wins == [True] * 16
    assert ss.get(order_key) is None
    for j in jobs:
        fk = ks.lock_key(j, 1000)
        # fence and proc landed on the fence's OWN shard
        assert shards[shard_index(fk, 4)].get(fk) is not None
        pk = ks.proc_key("nodeX", "g", j, 1)
        assert shards[shard_index(pk, 4)].get(pk) is not None
    # a second claim of the same fences loses on every item
    ss.put(order_key, "[]")
    wins2 = ss.claim_bundle(order_key, items, lease, lease)
    assert wins2 == [False] * 16
    assert ss.get(order_key) is None
    ss.close()


def test_claim_bundle_foreign_proc_key_still_registered():
    """A winner whose proc key hashes OFF its fence's shard (a foreign
    key shape that defeats job-token co-location) is stripped from the
    single-shard claim but still registered via a routed put after it
    — the claim/claim_many contract; a won fence must never silently
    lose its proc registration."""
    shards = [MemStore() for _ in range(4)]
    ss = ShardedStore(shards)
    fence = ks.lock_key("fp-job", 2000)
    fi = shard_index(fence, 4)
    # a proc key OUTSIDE the token map routes by its full text; pick
    # one that provably lands on a different shard than the fence
    pk = next(f"/elsewhere/proc-{n}" for n in range(64)
              if shard_index(f"/elsewhere/proc-{n}", 4) != fi)
    lease = ss.grant(30.0)
    for claim_fn in (
            lambda: ss.claim_bundle("", [(fence, "v", pk, "pv")],
                                    lease, lease),
            lambda: ss.claim_bundle_many(
                [("", [(ks.lock_key("fp-job2", 2000), "v", pk, "pv")])],
                lease, lease)[0]):
        wins = claim_fn()
        assert wins == [True]
        got = shards[shard_index(pk, 4)].get(pk)
        assert got is not None and got.value == "pv"
        assert ss.delete(pk)
    # a LOSING item's foreign proc key is not written
    wins = ss.claim_bundle("", [(fence, "v", pk, "pv")], lease, lease)
    assert wins == [False]
    assert ss.get(pk) is None
    ss.close()


def test_claim_bundle_many_exclusive_across_racing_clients():
    """Two routing clients over the SAME shard set race for the same
    backlog of bundles: every (job, second) fence is won exactly once
    fleet-wide — the global exactly-once contract survives the split,
    because a fence key routes identically whoever claims it."""
    shards = [MemStore() for _ in range(4)]
    a, b = ShardedStore(shards), ShardedStore(shards, verify_map=False)
    bundles = []
    for sec in range(6):
        okey = ks.dispatch_bundle_key("nodeY", 2000 + sec)
        a.put(okey, "bundle")
        items = [(ks.lock_key(f"rj{i}", 2000 + sec), "claimer", "", "")
                 for i in range(12)]
        bundles.append((okey, items))
    la, lb = a.grant(30.0), b.grant(30.0)
    out = {}
    barrier = threading.Barrier(2)

    def race(client, lease, tag):
        barrier.wait()
        out[tag] = client.claim_bundle_many(bundles, lease, lease)

    ta = threading.Thread(target=race, args=(a, la, "a"))
    tb = threading.Thread(target=race, args=(b, lb, "b"))
    ta.start(); tb.start(); ta.join(); tb.join()
    for wa, wb in zip(out["a"], out["b"]):
        for ia, ib in zip(wa, wb):
            assert ia != ib, "a (job, second) fence was won twice (or "\
                             "zero times) across racing sharded clients"
    for okey, _items in bundles:
        assert a.get(okey) is None
    a.close()


# ---------------------------------------------------------------- leases

def test_composite_lease_expiry_spans_shards():
    clocks = [time.monotonic] * 3
    shards = [MemStore(clock=c) for c in clocks]
    ss = ShardedStore(shards)
    lease = ss.grant(0.2)
    keys = [ks.job_key("g", f"lj{i}") for i in range(9)]
    ss.put_many([(k, "v") for k in keys], lease=lease)
    assert ss.keepalive(lease)
    assert ss.lease_ttl_remaining(lease) is not None
    assert ss.revoke(lease)
    # revoke dropped the attached keys on EVERY shard
    assert all(kv is None for kv in ss.get_many(keys))
    assert not ss.keepalive(lease)
    assert ss.lease_ttl_remaining(lease) is None
    ss.close()


def test_clone_shares_composite_lease_registry():
    ss = ShardedStore([MemStore() for _ in range(2)])
    lane = ss.clone()
    lease = ss.grant(30.0)
    # a lease granted on the main client works from a publisher lane
    lane.put(ks.job_key("g", "cl1"), "v", lease=lease)
    assert ss.get(ks.job_key("g", "cl1")).value == "v"
    ss.revoke(lease)
    assert ss.get(ks.job_key("g", "cl1")) is None
    ss.close()


# ---------------------------------------------------------------- watch

def test_watch_merge_preserves_per_shard_order_and_resumes():
    ss = ShardedStore([MemStore() for _ in range(3)])
    w = ss.watch(ks.node)
    keys = [ks.node_key(f"wn{i}") for i in range(24)]
    for k in keys:
        ss.put(k, "alive")
    seen, per_shard = [], {}
    while len(seen) < 24:
        ev = w.get(timeout=2.0)
        assert ev is not None, f"merged stream starved at {len(seen)}"
        seen.append(ev.kv.key)
        per_shard.setdefault(shard_index(ev.kv.key, 3),
                             []).append(ev.kv.mod_rev)
    assert sorted(seen) == sorted(keys)
    # per-shard ordering: each shard's events arrive in revision order
    for revs in per_shard.values():
        assert revs == sorted(revs)
    rv = w.rev_vector()
    assert len(rv) == 3
    w.close()
    # resume from the vector: nothing replays, new events flow
    w2 = ss.watch(ks.node, start_rev=rv)
    assert w2.get(timeout=0.3) is None
    # a shard that delivered nothing since resume reports its RESUME
    # point back, not 0 ("resume live" — which would skip its backlog
    # on the next resume)
    assert w2.rev_vector() == rv
    ss.put(ks.node_key("wn-new"), "alive")
    ev = w2.get(timeout=2.0)
    assert ev is not None and ev.kv.key == ks.node_key("wn-new")
    w2.close()
    ss.close()


def test_watch_scalar_resume_rejected_on_sharded():
    ss = ShardedStore([MemStore() for _ in range(2)])
    with pytest.raises(ValueError):
        ss.watch(ks.node, start_rev=7)
    with pytest.raises(ValueError):
        ss.watch(ks.node, start_rev=[1, 2, 3])   # wrong vector arity
    ss.close()


def test_one_lossy_shard_loses_merged_stream():
    """One shard's stream overflowing makes the MERGED stream lossy:
    buffered tail first, then WatchLost — the same re-list contract a
    single store's consumers already implement."""
    shards = [MemStore() for _ in range(2)]
    ss = ShardedStore(shards)
    w = ss.watch(ks.node)
    # find a key on each shard, then overflow shard 1's child stream
    by_shard = {}
    i = 0
    while len(by_shard) < 2:
        k = ks.node_key(f"lk{i}")
        by_shard.setdefault(shard_index(k, 2), k)
        i += 1
    ss.put(by_shard[0], "kept")           # healthy shard's event
    time.sleep(0.1)                        # let it reach the merge queue
    w._children[1]._max_backlog = 4        # shrink, then overflow
    for n in range(32):
        ss.put(by_shard[1], f"flood{n}")
    got, lost = [], False
    deadline = time.time() + 5
    while time.time() < deadline:
        try:
            ev = w.get(timeout=0.3)
        except WatchLost:
            lost = True
            break
        if ev is not None:
            got.append(ev.kv.key)
    assert lost, "merged stream never surfaced the lossy shard"
    assert by_shard[0] in got, "buffered tail was dropped, not drained"
    w.close()
    ss.close()


# ---------------------------------------------------------------- topology

def test_shard_map_mismatch_refused():
    shards = [MemStore() for _ in range(3)]
    ss = ShardedStore(shards)                 # pins {"n": 3, ...}
    kv = shards[0].get(ks.shardmap)
    assert kv is not None
    assert json.loads(kv.value) == {"n": 3, "hash": HASH_SCHEME}
    with pytest.raises(RuntimeError, match="shard-map mismatch"):
        ShardedStore(shards[:2])              # 2-shard client, 3-shard set
    ss.close()


def test_single_address_client_refused_on_sharded_layout():
    """A stale one-store config pointed at shard 0 of a multi-shard
    layout must refuse (it would fence every job on one shard and race
    the fleet), not silently serve; an un-pinned store passes."""
    m = MemStore()
    verify_single_store(m)                    # no pin laid out: fine
    shards = [m, MemStore()]
    ss = ShardedStore(shards)                 # pins {"n": 2, ...}
    with pytest.raises(RuntimeError, match="shard-map mismatch"):
        verify_single_store(m)
    ss.close()


def test_single_shard_is_passthrough():
    """One shard: no shard map written, scalar revisions and scalar
    watch resume accepted — behaviorally identical to a plain client."""
    m = MemStore()
    ss = ShardedStore([m])
    ss.put(ks.job_key("g", "solo"), "v")
    assert m.get(ks.shardmap) is None
    assert isinstance(ss.rev(), int)
    w = ss.watch(ks.cmd, start_rev=1)         # scalar resume allowed
    ev = w.get(timeout=2.0)
    assert ev is not None and ev.kv.key == ks.job_key("g", "solo")
    w.close()
    ss.close()


# ------------------------------------------------------- prefix pinning

class _CountingStore(MemStore):
    """MemStore that counts prefix-op calls, to pin which shards a
    routed prefix op actually touches."""

    def __init__(self):
        super().__init__()
        self.calls = {"get_prefix": 0, "count_prefix": 0,
                      "delete_prefix": 0, "watch": 0}

    def get_prefix(self, prefix):
        self.calls["get_prefix"] += 1
        return super().get_prefix(prefix)

    def count_prefix(self, prefix):
        self.calls["count_prefix"] += 1
        return super().count_prefix(prefix)

    def delete_prefix(self, prefix):
        self.calls["delete_prefix"] += 1
        return super().delete_prefix(prefix)

    def watch(self, prefix, start_rev=0, max_backlog=None, events=""):
        self.calls["watch"] += 1
        return super().watch(prefix, start_rev=start_rev,
                             max_backlog=max_backlog, events=events)


def test_prefix_token_pins_only_closed_segments():
    p = prefix_shard_token
    assert p("/cronsun/dispatch/A/") == "n:A"
    assert p("/cronsun/dispatch/A") is None      # also matches node "AB"
    assert p("/cronsun/dispatch/_all/") == "n:_all"
    assert p("/cronsun/node/A/") == "n:A"
    assert p("/cronsun/lock/j5/") == "j:j5"
    assert p("/cronsun/lock/") is None
    # the bare …/lock/alone/ key itself routes by "j:alone" while keys
    # below it route by the job — not pinnable
    assert p("/cronsun/lock/alone/") is None
    assert p("/cronsun/lock/alone/j5/") == "j:j5"
    assert p("/cronsun/proc/n1/g1/j1/") == "j:j1"
    assert p("/cronsun/proc/n1/") is None
    assert p("/cronsun/cmd/g1/") is None
    assert p("/cronsun/cmd/g1/j1/") == "j:j1"
    assert p("/cronsun/") is None
    assert p("/other/x/") is None


def test_prefix_token_agrees_with_every_key_under_it():
    # the pin is sound: ANY key extending a pinned prefix routes by it
    for pfx in ("/cronsun/dispatch/A/", "/cronsun/lock/j5/",
                "/cronsun/lock/alone/j5/", "/cronsun/proc/n/g/j/",
                "/cronsun/once/g/j/", "/cronsun/node/A/"):
        tok = prefix_shard_token(pfx)
        assert tok is not None, pfx
        for tail in ("", "x", "1234", "a/b/c", "alone", "j5/9"):
            assert shard_token(pfx + tail) == tok, (pfx, tail)


def test_pinned_prefix_ops_touch_one_shard():
    """An agent's dispatch re-list/count hits the ONE shard its node
    token lives on; an unpinnable prefix still fans to all shards."""
    shards = [_CountingStore() for _ in range(4)]
    ss = ShardedStore(shards)
    pfx = ks.dispatch + "A/"
    keys = [ks.dispatch_bundle_key("A", 100 + i) for i in range(6)]
    for k in keys:
        ss.put(k, "[]")
    got = [kv.key for kv in ss.get_prefix(pfx)]
    assert got == sorted(keys)
    assert sum(s.calls["get_prefix"] for s in shards) == 1
    assert ss.count_prefix(pfx) == 6
    assert sum(s.calls["count_prefix"] for s in shards) == 1
    assert ss.delete_prefix(pfx) == 6
    assert sum(s.calls["delete_prefix"] for s in shards) == 1
    # unpinnable prefix: full fan-out
    ss.get_prefix(ks.node)
    assert sum(s.calls["get_prefix"] for s in shards) == 1 + 4
    ss.close()


def test_pinned_watch_single_stream_full_rev_vector():
    """A token-pinned watch opens ONE underlying stream but still
    speaks the full-length revision vector, so resume round-trips
    through the same watch() contract as a fanned watch."""
    shards = [_CountingStore() for _ in range(3)]
    ss = ShardedStore(shards)
    pfx = ks.dispatch + "A/"
    w = ss.watch(pfx)
    assert sum(s.calls["watch"] for s in shards) == 1
    ss.put(ks.dispatch_bundle_key("A", 100), "[]")
    ev = w.get(timeout=2.0)
    assert ev is not None
    assert ev.kv.key == ks.dispatch_bundle_key("A", 100)
    rv = w.rev_vector()
    assert len(rv) == 3
    w.close()
    w2 = ss.watch(pfx, start_rev=rv)
    assert w2.get(timeout=0.3) is None           # nothing replays
    assert w2.rev_vector() == rv                 # quiet != regressed
    ss.put(ks.dispatch_bundle_key("A", 101), "[]")
    ev = w2.get(timeout=2.0)
    assert ev is not None
    assert ev.kv.key == ks.dispatch_bundle_key("A", 101)
    w2.close()
    ss.close()


def test_clone_close_leaves_aliased_parent_shards_alive():
    """A clone over shard clients with no clone() of their own
    (MemStore) aliases the parent's shards; closing the lane must not
    close them — the parent's watchers and KV surface stay live."""
    ss = ShardedStore([MemStore() for _ in range(2)])
    w = ss.watch(ks.node)
    lane = ss.clone()
    lane.close()
    k = ks.node_key("alive-after-lane-close")
    ss.put(k, "v")
    ev = w.get(timeout=2.0)
    assert ev is not None and ev.kv.key == k
    assert ss.get(k).value == "v"
    w.close()
    ss.close()


# ------------------------------------------------------- py<->native wire

def _shard_servers(backend, n):
    servers = []
    if backend == "native":
        binary = find_binary()
        if binary is None:
            pytest.skip("native store binary unavailable")
        for _ in range(n):
            servers.append(NativeStoreServer(binary=binary))
    else:
        for _ in range(n):
            servers.append(StoreServer(MemStore()).start())
    return servers


@pytest.mark.parametrize("backend", ["py", "native"])
@pytest.mark.parametrize("nshards", [1, 2, 4])
def test_wire_parity_across_backends(backend, nshards):
    """The routed client over real store servers — Python and native —
    at 1/2/4 shards: routing, split bulk ops, bundle claims, merged
    watches, and the shard-map pin behave identically."""
    servers = _shard_servers(backend, nshards)
    addrs = [f"{s.host}:{s.port}" for s in servers]
    store = connect_sharded(addrs)
    try:
        if nshards == 1:
            assert isinstance(store, RemoteStore)   # pure passthrough
        else:
            assert store.nshards == nshards
        items = [(ks.job_key("g", f"wj{i}"), f"v{i}") for i in range(20)]
        store.put_many(items)
        got = store.get_many([k for k, _ in items])
        assert [kv.value for kv in got] == [v for _, v in items]

        w = store.watch(ks.dispatch)
        order_key = ks.dispatch_bundle_key("wnode", 500)
        store.put(order_key, json.dumps([f"g/wj{i}" for i in range(20)]))
        ev = w.get(timeout=5.0)
        assert ev is not None and ev.kv.key == order_key

        lease = store.grant(30.0)
        claims = [(ks.lock_key(f"wj{i}", 500), "wnode",
                   ks.proc_key("wnode", "g", f"wj{i}", 1), "pv")
                  for i in range(20)]
        wins = store.claim_bundle(order_key, claims, lease, lease)
        assert wins == [True] * 20
        assert store.get(order_key) is None
        # the delete reached the merged stream too
        deadline = time.time() + 5
        deleted = False
        while time.time() < deadline and not deleted:
            ev = w.get(timeout=0.5)
            deleted = ev is not None and ev.kv.key == order_key
        assert deleted
        w.close()
        store.keepalive(lease)
        store.revoke(lease)
        assert store.get(ks.proc_key("wnode", "g", "wj0", 1)) is None

        if nshards > 1:
            # a second client with the WRONG count is refused
            with pytest.raises(RuntimeError, match="shard-map"):
                bad = connect_sharded(addrs + addrs[:1])   # n+1 shards
                bad.close()
    finally:
        store.close()
        for s in servers:
            s.stop()
