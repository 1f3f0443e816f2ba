"""RemoteStore conformance over BOTH server backends: the Python
StoreServer and the native C++ cronsun-stored must behave exactly like
MemStore — KV revisions, prefix watches with prev-kv, leases, CAS txns,
bulk puts, and watch replay from a revision.  One suite, two backends."""

import time

import pytest

from cronsun_tpu.store import CompactedError, MemStore
from cronsun_tpu.store.native import NativeStoreServer, find_binary
from cronsun_tpu.store.remote import RemoteStore, StoreServer

BACKENDS = ["py", "native"]


def _make_server(backend, history=65536):
    if backend == "py":
        return StoreServer(MemStore(history=history)).start()
    binary = find_binary()
    if binary is None:
        pytest.skip("native store binary unavailable")
    return NativeStoreServer(binary=binary, history=history)


@pytest.fixture(params=BACKENDS)
def remote(request):
    srv = _make_server(request.param)
    client = RemoteStore(srv.host, srv.port)
    aux = RemoteStore(srv.host, srv.port)   # independent connection
    yield srv, client, aux
    client.close()
    aux.close()
    srv.stop()


def test_kv_roundtrip_and_revisions(remote):
    _, s, _ = remote
    r1 = s.put("/a", "1")
    r2 = s.put("/a", "2")
    assert r2 == r1 + 1
    kv = s.get("/a")
    assert kv.value == "2" and kv.create_rev == r1 and kv.mod_rev == r2
    assert s.get("/missing") is None
    s.put("/a/b", "x")
    assert [kv.key for kv in s.get_prefix("/a")] == ["/a", "/a/b"]
    assert s.count_prefix("/a") == 2
    assert s.delete("/a") is True
    assert s.delete("/a") is False
    assert s.delete_prefix("/a") == 1


def test_txns(remote):
    _, s, _ = remote
    assert s.put_if_absent("/lock", "me") is True
    assert s.put_if_absent("/lock", "you") is False
    kv = s.get("/lock")
    assert kv.value == "me"
    assert s.put_if_mod_rev("/lock", "me2", kv.mod_rev) is True
    assert s.put_if_mod_rev("/lock", "me3", kv.mod_rev) is False


def test_leases_expire_and_keepalive(remote):
    _, s, _ = remote
    l = s.grant(0.4)
    s.put("/leased", "v", lease=l)
    assert s.get("/leased") is not None
    for _ in range(4):
        time.sleep(0.15)
        s.keepalive(l)
    assert s.get("/leased") is not None          # keepalive held it
    time.sleep(0.8)
    assert s.get("/leased") is None              # expired server-side
    assert s.keepalive(l) is False
    with pytest.raises(KeyError):
        s.put("/x", "y", lease=l)


@pytest.mark.parametrize("backend", BACKENDS)
def test_lease_survives_client_disconnect(backend):
    """etcd semantics: a dropped connection closes watches, not leases."""
    srv = _make_server(backend)
    c1 = RemoteStore(srv.host, srv.port)
    l = c1.grant(30)
    c1.put("/k", "v", lease=l)
    c1.close()
    time.sleep(0.3)
    c2 = RemoteStore(srv.host, srv.port)
    assert c2.get("/k") is not None
    assert c2.keepalive(l) is True
    c2.close()
    srv.stop()


def test_watch_stream_and_prev_kv(remote):
    _, s, _ = remote
    w = s.watch("/jobs/")
    s.put("/jobs/a", "1")
    s.put("/jobs/a", "2")
    s.put("/other", "x")
    s.delete("/jobs/a")
    evs = []
    deadline = time.time() + 3
    while len(evs) < 3 and time.time() < deadline:
        ev = w.get(timeout=0.2)
        if ev:
            evs.append(ev)
    assert [e.type for e in evs] == ["PUT", "PUT", "DELETE"]
    assert evs[0].is_create and evs[1].is_modify
    assert evs[1].prev_kv.value == "1"
    assert evs[2].prev_kv.value == "2"
    w.close()
    s.put("/jobs/b", "3")
    time.sleep(0.2)
    assert w.drain() == []


def test_watch_replay_from_revision(remote):
    _, s, _ = remote
    r = s.put("/w/a", "1")
    s.put("/w/b", "2")
    s.put("/w/c", "3")
    w = s.watch("/w/", start_rev=r + 1)          # resume after the first
    evs = []
    deadline = time.time() + 3
    while len(evs) < 2 and time.time() < deadline:
        ev = w.get(timeout=0.2)
        if ev:
            evs.append(ev)
    assert [e.kv.key for e in evs] == ["/w/b", "/w/c"]
    # live events still flow after the replay
    s.put("/w/d", "4")
    ev = w.get(timeout=2)
    assert ev is not None and ev.kv.key == "/w/d"
    w.close()


def test_watch_replay_compaction():
    s = MemStore(history=4)
    for i in range(10):
        s.put(f"/k{i}", "v")
    with pytest.raises(CompactedError):
        s.watch("/k", start_rev=2)
    w = s.watch("/k", start_rev=7)               # still retained
    assert [e.kv.key for e in w.drain()] == ["/k6", "/k7", "/k8", "/k9"]
    s.close()


@pytest.mark.parametrize("backend", BACKENDS)
def test_watch_replay_compaction_over_wire(backend):
    """Same compaction contract over the wire against both servers."""
    srv = _make_server(backend, history=4)
    s = RemoteStore(srv.host, srv.port)
    try:
        for i in range(10):
            s.put(f"/k{i}", "v")
        with pytest.raises(CompactedError):
            s.watch("/k", start_rev=2)
        w = s.watch("/k", start_rev=7)
        evs = []
        deadline = time.time() + 3
        while len(evs) < 4 and time.time() < deadline:
            ev = w.get(timeout=0.2)
            if ev:
                evs.append(ev)
        assert [e.kv.key for e in evs] == ["/k6", "/k7", "/k8", "/k9"]
    finally:
        s.close()
        srv.stop()


def test_put_many_single_roundtrip(remote):
    _, s, aux = remote
    items = [[f"/bulk/{i}", str(i)] for i in range(100)]
    rev = s.put_many(items)
    assert s.count_prefix("/bulk/") == 100
    assert aux.get("/bulk/99").mod_rev == rev
    l = s.grant(30)
    s.put_many([["/bulk-leased/a", "1"]], lease=l)
    s.revoke(l)
    assert s.get("/bulk-leased/a") is None


def test_concurrent_clients_contend_for_lock(remote):
    srv, _, _ = remote
    import threading
    wins = []
    def worker():
        c = RemoteStore(srv.host, srv.port)
        if c.put_if_absent("/the-lock", "x"):
            wins.append(1)
        c.close()
    ts = [threading.Thread(target=worker) for _ in range(8)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert len(wins) == 1


def test_client_heals_connection_and_resumes_watch(remote):
    """A broken TCP connection must not kill the client: calls fail
    transiently, then the store reconnects and re-establishes watches
    from their last seen revision (no deltas lost)."""
    srv, s, aux = remote
    w = s.watch("/heal/")
    s.put("/heal/a", "1")
    ev = w.get(timeout=2)
    assert ev is not None and ev.kv.key == "/heal/a"
    # sever the TCP connection out from under the client
    s._sock.close()
    # events written while the client is down...
    aux.put("/heal/b", "2")
    # ...are replayed after the heal
    deadline = time.time() + 10
    got = []
    while time.time() < deadline and len(got) < 1:
        ev = w.get(timeout=0.3)
        if ev:
            got.append(ev)
    assert [e.kv.key for e in got] == ["/heal/b"], f"got {got}"
    # plain RPCs work again too
    deadline = time.time() + 10
    while time.time() < deadline:
        try:
            s.put("/heal/c", "3")
            break
        except Exception:
            time.sleep(0.2)
    assert s.get("/heal/c").value == "3"
    ev = w.get(timeout=2)
    assert ev is not None and ev.kv.key == "/heal/c"


def test_native_wal_survives_kill9(tmp_path):
    """Durability (the reference's etcd persists to disk): with --wal,
    state — keys, exact revisions, live leases — survives a kill -9 and
    restart; the global revision continues, leased keys keep expiring."""
    binary = find_binary()
    if binary is None:
        pytest.skip("native store binary unavailable")
    wal = str(tmp_path / "store.wal")

    srv = NativeStoreServer(binary=binary, wal=wal)
    s = RemoteStore(srv.host, srv.port, reconnect=False)
    r1 = s.put("/jobs/a", "v1")
    r2 = s.put("/jobs/a", "v2")
    s.put("/jobs/b", "x")
    s.delete("/jobs/b")
    lease = s.grant(30)
    s.put("/leased", "l", lease=lease)
    short = s.grant(1.0)
    s.put("/short", "gone-soon", lease=short)
    time.sleep(0.3)   # WAL flushes immediately; sync rides the sweeper
    srv._proc.kill()   # kill -9: no shutdown path runs
    srv._proc.wait()
    s.close()

    srv2 = NativeStoreServer(binary=binary, wal=wal)
    try:
        s2 = RemoteStore(srv2.host, srv2.port, reconnect=False)
        kv = s2.get("/jobs/a")
        assert kv is not None and kv.value == "v2"
        assert kv.create_rev == r1 and kv.mod_rev == r2
        assert s2.get("/jobs/b") is None
        # revision stream continues exactly where it left off
        r_next = s2.put("/after", "restart")
        assert r_next > r2
        # the 30s lease survived with its key; keepalive still works
        assert s2.get("/leased") is not None
        assert s2.keepalive(lease) is True
        # the 1s lease expires (either during downtime or right after)
        deadline = time.time() + 5
        while time.time() < deadline and s2.get("/short") is not None:
            time.sleep(0.1)
        assert s2.get("/short") is None, "expired lease key persisted"
        s2.close()
    finally:
        srv2.stop()


def test_native_wal_compacts_on_boot(tmp_path):
    """Boot rewrites the WAL as a snapshot: restarting twice after heavy
    overwrite traffic must shrink the file, not grow it without bound."""
    import os
    binary = find_binary()
    if binary is None:
        pytest.skip("native store binary unavailable")
    wal = str(tmp_path / "store.wal")
    srv = NativeStoreServer(binary=binary, wal=wal)
    s = RemoteStore(srv.host, srv.port, reconnect=False)
    for i in range(2000):
        s.put("/hot", f"value-{i}")   # one live key, 2000 log records
    s.close()
    srv.stop()
    size_before = os.path.getsize(wal)
    srv2 = NativeStoreServer(binary=binary, wal=wal)
    srv2.stop()
    size_after = os.path.getsize(wal)
    assert size_after < size_before / 10, (size_before, size_after)


def test_native_wal_replays_large_records(tmp_path):
    """Values have no length cap on the wire; WAL replay must handle
    records far larger than any fixed line buffer."""
    binary = find_binary()
    if binary is None:
        pytest.skip("native store binary unavailable")
    wal = str(tmp_path / "w.wal")
    srv = NativeStoreServer(binary=binary, wal=wal)
    s = RemoteStore(srv.host, srv.port, reconnect=False)
    big = "x" * 200_000
    s.put("/big", big)
    s.close()
    srv._proc.kill()
    srv._proc.wait()
    srv2 = NativeStoreServer(binary=binary, wal=wal)
    try:
        s2 = RemoteStore(srv2.host, srv2.port, reconnect=False)
        kv = s2.get("/big")
        assert kv is not None and kv.value == big
        s2.close()
    finally:
        srv2.stop()


def test_watch_lost_propagates_over_wire():
    """A server-side slow-watcher cancellation reaches the remote client
    as WatchLost (not a silent starve): consumer re-lists + re-watches."""
    from cronsun_tpu.store.memstore import WatchLost
    srv = StoreServer().start()
    s = RemoteStore(srv.host, srv.port)
    w = s.watch("/lw/")
    s.put("/lw/seed", "0")
    assert w.get(timeout=3) is not None
    # shrink the SERVER-side watcher backlog and blast past it
    for sw in list(srv.store._watchers):
        if sw.prefix == "/lw/":
            sw._max_backlog = 3
    for i in range(20):
        srv.store.put(f"/lw/{i}", "x")
    deadline = time.time() + 5
    got_lost = False
    while time.time() < deadline:
        try:
            if w.get(timeout=0.2) is None and w.lost:
                pass
        except WatchLost:
            got_lost = True
            break
    assert got_lost, "client never learned the stream was lost"
    # re-list + fresh watch resynchronizes
    assert s.count_prefix("/lw/") == 21
    w2 = s.watch("/lw/")
    s.put("/lw/new", "y")
    ev = w2.get(timeout=3)
    assert ev is not None
    s.close()
    srv.stop()


# ---------------------------------------------------------------- auth

def _make_secured(backend, token):
    if backend == "py":
        return StoreServer(MemStore(), token=token).start()
    binary = find_binary()
    if binary is None:
        pytest.skip("native store binary unavailable")
    return NativeStoreServer(binary=binary, token=token)


@pytest.mark.parametrize("backend", BACKENDS)
def test_auth_required_when_token_set(backend):
    """With a shared secret configured, a wrong-token (or token-less)
    client is refused before any op executes; the right token works
    across the full surface including watches (the reference carries
    etcd credentials in config, conf/conf.go:66-67)."""
    from cronsun_tpu.store.remote import RemoteStoreError
    srv = _make_secured(backend, "s3cret")
    try:
        # no token: first real op is rejected and the connection closed
        bad = RemoteStore(srv.host, srv.port, reconnect=False)
        with pytest.raises(RemoteStoreError):
            bad.put("/a", "1")
        bad.close()
        # wrong token: the handshake itself fails
        with pytest.raises(RemoteStoreError):
            RemoteStore(srv.host, srv.port, reconnect=False,
                        token="wrong")
        # right token: everything works, including watch push
        good = RemoteStore(srv.host, srv.port, reconnect=False,
                           token="s3cret")
        w = good.watch("/a/")
        good.put("/a/k", "v")
        assert good.get("/a/k").value == "v"
        ev = w.get(timeout=3)
        assert ev is not None and ev.kv.value == "v"
        good.close()
        # the refused client must not have written anything
        chk = RemoteStore(srv.host, srv.port, reconnect=False,
                          token="s3cret")
        assert chk.get("/a") is None
        chk.close()
    finally:
        srv.stop()


@pytest.mark.parametrize("backend", BACKENDS)
def test_auth_noop_when_unsecured(backend):
    """A client configured with a token still works against an open
    server (the auth op is a no-op) — lets a fleet roll tokens out
    client-first."""
    srv = _make_server(backend)
    try:
        s = RemoteStore(srv.host, srv.port, reconnect=False, token="x")
        s.put("/k", "v")
        assert s.get("/k").value == "v"
        s.close()
    finally:
        srv.stop()


@pytest.mark.parametrize("backend", BACKENDS)
def test_malformed_frames_do_not_crash_server(backend):
    """Garbage bytes, truncated JSON, wrong-typed fields and huge lines
    must at worst close the offending connection — the server keeps
    serving well-behaved clients."""
    import socket as _s
    srv = _make_server(backend)
    try:
        good = RemoteStore(srv.host, srv.port, reconnect=False)
        good.put("/health", "1")
        payloads = [
            b"\x00\xff\xfe garbage\n",
            b"{\"i\": 1, \"o\": \"put\"",          # truncated, no newline
            b"{\"i\": 1, \"o\": \"put\"}\n" * 3,   # missing args
            b"{\"i\": \"x\", \"o\": 42, \"a\": {}}\n",
            b"[1,2,3]\n",
            b"{\"i\": 1, \"o\": \"watch\", \"a\": [7, \"x\"]}\n",
            b"{\"i\": 1, \"o\": \"put\", \"a\": [\"/k\", "
            + b"\"" + b"v" * 300_000 + b"\"]}\n",  # huge but valid
            b"{\"i\": 1, \"o\": \"grant\", \"a\": [\"NaN\"]}\n",
        ]
        for p in payloads:
            c = _s.create_connection((srv.host, srv.port), timeout=5)
            try:
                c.sendall(p)
                c.settimeout(1.0)
                try:
                    c.recv(4096)
                except (TimeoutError, OSError):
                    pass
            finally:
                c.close()
        # the server survived all of it and still serves
        assert good.get("/health").value == "1"
        good.put("/health", "2")
        assert good.get("/health").value == "2"
        good.close()
    finally:
        srv.stop()


def test_differential_fuzz_python_vs_native():
    """Differential fuzz: one random KV/txn op sequence applied to BOTH
    store backends must produce identical revisions and contents
    (leases/watches excluded — they are timing-dependent and covered by
    the scenario tests)."""
    import random
    rng = random.Random(42)
    py = _make_server("py")
    binary = find_binary()
    if binary is None:
        py.stop()
        pytest.skip("native store binary unavailable")
    nt = NativeStoreServer(binary=binary)
    a = RemoteStore(py.host, py.port, reconnect=False)
    b = RemoteStore(nt.host, nt.port, reconnect=False)

    def rs(n=6):
        return "".join(rng.choice("ab/ζ%\\\"'xyz0 ") for _ in range(n))

    keys = [f"/f/{i}" for i in range(8)] + ["/f/sub/x", "/g/1"]
    try:
        for step in range(400):
            op = rng.randrange(10)
            k = rng.choice(keys)
            if op <= 3:
                v = rs(rng.randrange(0, 30))
                ra, rb = a.put(k, v), b.put(k, v)
                assert ra == rb, f"step {step}: put rev {ra} != {rb}"
            elif op == 4:
                ra, rb = a.delete(k), b.delete(k)
                assert ra == rb, f"step {step}: delete {ra} != {rb}"
            elif op == 5:
                v = rs()
                ra, rb = (a.put_if_absent(k, v), b.put_if_absent(k, v))
                assert ra == rb, f"step {step}: put_if_absent {ra} != {rb}"
            elif op == 6:
                kva, kvb = a.get(k), b.get(k)
                assert kva == kvb, f"step {step}: get({k}) differs"
                mr = kva.mod_rev if kva and rng.random() < 0.7 else \
                    rng.randrange(1, 50)
                v = rs()
                ra, rb = (a.put_if_mod_rev(k, v, mr),
                          b.put_if_mod_rev(k, v, mr))
                assert ra == rb, f"step {step}: CAS {ra} != {rb}"
            elif op == 7:
                pfx = rng.choice(["/f/", "/f/sub/", "/g/", "/", "/nope/"])
                ra = [(kv.key, kv.value, kv.create_rev, kv.mod_rev)
                      for kv in a.get_prefix(pfx)]
                rb = [(kv.key, kv.value, kv.create_rev, kv.mod_rev)
                      for kv in b.get_prefix(pfx)]
                assert ra == rb, f"step {step}: prefix {pfx} differs"
            elif op == 8:
                pfx = rng.choice(["/f/", "/g/", "/"])
                assert a.count_prefix(pfx) == b.count_prefix(pfx), \
                    f"step {step}: count {pfx}"
            else:
                items = [(rng.choice(keys), rs()) for _ in range(3)]
                ra, rb = a.put_many(items), b.put_many(items)
                assert ra == rb, f"step {step}: put_many rev {ra} != {rb}"
        fa = [(kv.key, kv.value, kv.create_rev, kv.mod_rev)
              for kv in a.get_prefix("/")]
        fb = [(kv.key, kv.value, kv.create_rev, kv.mod_rev)
              for kv in b.get_prefix("/")]
        assert fa == fb, "final keyspaces diverged"
    finally:
        a.close()
        b.close()
        py.stop()
        nt.stop()


def test_claim_semantics(remote):
    """store.claim: atomic fence + proc put + order delete in one op —
    both backends must agree bit-for-bit (the agents' hot path)."""
    _, s, s2 = remote
    fl = s.grant(30.0)
    pl = s.grant(30.0)
    s.put("/d/n1/100/g/j", "order")
    # winning claim: fence written, proc written, order consumed
    assert s.claim("/lk/j/100", "n1", fl, "/d/n1/100/g/j",
                   "/pr/n1/g/j/100", '{"t":1}', pl) is True
    assert s.get("/lk/j/100").value == "n1"
    assert s.get("/pr/n1/g/j/100").value == '{"t":1}'
    assert s.get("/d/n1/100/g/j") is None
    # losing claim from another connection: order consumed, nothing else
    s2.put("/d/n2/100/g/j", "order")
    assert s2.claim("/lk/j/100", "n2", fl, "/d/n2/100/g/j",
                    "/pr/n2/g/j/100", "{}", pl) is False
    assert s2.get("/d/n2/100/g/j") is None
    assert s2.get("/pr/n2/g/j/100") is None
    assert s2.get("/lk/j/100").value == "n1"
    # leases own their keys: revoking the proc lease kills only the proc
    s.revoke(pl)
    assert s.get("/pr/n1/g/j/100") is None
    assert s.get("/lk/j/100") is not None
    # optional keys: claim with no order/proc is a bare fence
    assert s.claim("/lk/j/101", "n1", fl) is True
    assert s.claim("/lk/j/101", "n2", fl) is False
    # invalid lease raises without a half-applied claim
    with pytest.raises(KeyError):
        s.claim("/lk/j/102", "n1", 999999)
    assert s.get("/lk/j/102") is None
    with pytest.raises(KeyError):
        s.claim("/lk/j/103", "n1", fl, "", "/pr/x", "{}", 999999)
    assert s.get("/lk/j/103") is None           # fence not half-written


def test_claim_bundle_semantics(remote):
    """store.claim_bundle: one atomic op consumes a whole coalesced
    (node, second) order — per-job fences + winners' proc keys + ONE
    delete of the bundle key.  Both backends must agree bit-for-bit
    (the coalesced dispatch format's hot path)."""
    _, s, s2 = remote
    fl = s.grant(30.0)
    pl = s.grant(30.0)
    bundle = "/d/n1/200"
    s.put(bundle, '["g/a","g/b","g/c"]')
    # pre-take one fence: another node already ran (b, 200)
    assert s2.put_if_absent("/lk/b/200", "other") is True
    wins = s.claim_bundle(bundle, [
        ("/lk/a/200", "n1@1-1", "/pr/n1/g/a/200", '{"t":1}'),
        ("/lk/b/200", "n1@1-2", "/pr/n1/g/b/200", '{"t":2}'),
        ("/lk/c/200", "n1@1-3", "", ""),        # short-run suppression
        ("bad",),                               # malformed: per-item False
    ], fl, pl)
    assert wins == [True, False, True, False]
    # winners: fence + proc; loser: nothing beyond the existing fence
    assert s.get("/lk/a/200").value == "n1@1-1"
    assert s.get("/pr/n1/g/a/200").value == '{"t":1}'
    assert s.get("/lk/b/200").value == "other"
    assert s.get("/pr/n1/g/b/200") is None
    assert s.get("/lk/c/200").value == "n1@1-3"
    # the reservation key is consumed exactly once, win/lose mix or not
    assert s.get(bundle) is None
    # an invalid lease raises with NO half-applied bundle
    s.put("/d/n1/201", '["g/a"]')
    with pytest.raises(KeyError):
        s.claim_bundle("/d/n1/201",
                       [("/lk/a/201", "n1", "/pr/x", "{}")], fl, 999999)
    assert s.get("/lk/a/201") is None
    assert s.get("/d/n1/201") is not None
    # empty items still release the reservation
    assert s.claim_bundle("/d/n1/201", [], fl, pl) == []
    assert s.get("/d/n1/201") is None


def test_claim_bundle_many_semantics(remote):
    """store.claim_bundle_many: a backlog of coalesced bundles consumed
    in ONE atomic op — per-bundle win lists identical to claim_bundle,
    shared leases validated before any mutation, every reservation key
    deleted exactly once.  Both backends must agree bit-for-bit (the
    herd catch-up hot path)."""
    _, s, s2 = remote
    fl = s.grant(30.0)
    pl = s.grant(30.0)
    s.put("/dm/n1/300", '["g/a","g/b"]')
    s.put("/dm/n1/301", '["g/c"]')
    s.put("/dm/n1/302", '["g/d"]')
    # pre-take one fence: that member loses in the batch too
    assert s2.put_if_absent("/lkm/b/300", "other") is True
    wins = s.claim_bundle_many([
        ("/dm/n1/300", [("/lkm/a/300", "n1@1-1", "/prm/a/300", '{"t":1}'),
                        ("/lkm/b/300", "n1@1-2", "/prm/b/300", '{"t":2}')]),
        ("/dm/n1/301", [("/lkm/c/301", "n1@1-3", "", "")]),
        ("/dm/n1/302", [("bad",)]),         # malformed item: per-item False
    ], fl, pl)
    assert wins == [[True, False], [True], [False]]
    assert s.get("/lkm/a/300").value == "n1@1-1"
    assert s.get("/prm/a/300").value == '{"t":1}'
    assert s.get("/lkm/b/300").value == "other"
    assert s.get("/prm/b/300") is None
    assert s.get("/lkm/c/301").value == "n1@1-3"
    # every reservation key consumed, including the all-malformed bundle
    for k in ("/dm/n1/300", "/dm/n1/301", "/dm/n1/302"):
        assert s.get(k) is None, k
    # an invalid lease raises with NO half-applied batch
    s.put("/dm/n1/303", '["g/e"]')
    with pytest.raises(KeyError):
        s.claim_bundle_many(
            [("/dm/n1/303", [("/lkm/e/303", "n1", "/prm/e", "{}")])],
            fl, 999999)
    assert s.get("/lkm/e/303") is None
    assert s.get("/dm/n1/303") is not None
    # empty batch is a no-op; empty items still release the reservation
    assert s.claim_bundle_many([], fl, pl) == []
    assert s.claim_bundle_many([("/dm/n1/303", [])], fl, pl) == [[]]
    assert s.get("/dm/n1/303") is None


def test_op_stats_counts_hot_ops(remote):
    """Per-op server-side timing (claim paths, bulk writes, watch
    fan-out) is queryable over the wire on both backends — the bench
    uses it to attribute the dispatch-plane ceiling."""
    _, s, _ = remote
    s.put_many([(f"/os/{i}", "v") for i in range(5)])
    fl = s.grant(30.0)
    s.claim("/os-lk/1", "n", fl)
    s.claim_bundle("", [("/os-lk/2", "n", "", "")], fl, 0)
    stats = s.op_stats()
    for op in ("put_many", "claim", "claim_bundle"):
        assert stats[op]["count"] >= 1, (op, stats)
        assert stats[op]["total_ms"] >= 0
        assert stats[op]["max_ms"] >= 0
    assert stats["watch_fanout"]["count"] >= 1


def test_delete_many(remote):
    _, s, _ = remote
    s.put_many([(f"/dm/{i}", "v") for i in range(10)])
    assert s.delete_many([f"/dm/{i}" for i in range(7)] + ["/missing"]) == 7
    assert s.count_prefix("/dm/") == 3


def test_claim_events_flow_to_watchers(remote):
    """Claims are regular mutations: watch streams see the fence PUT,
    proc PUT and order DELETE (mirrors depend on this)."""
    _, s, s2 = remote
    w_lock = s2.watch("/lk2/")
    w_proc = s2.watch("/pr2/")
    w_disp = s2.watch("/d2/")
    s.put("/d2/n1/5/g/j", "o")
    fl = s.grant(30.0)
    assert s.claim("/lk2/j/5", "n1", fl, "/d2/n1/5/g/j",
                   "/pr2/n1/g/j/5", "{}", fl) is True
    deadline = time.time() + 5
    evs = {"lock": [], "proc": [], "disp": []}
    while time.time() < deadline:
        evs["lock"] += w_lock.drain()
        evs["proc"] += w_proc.drain()
        evs["disp"] += w_disp.drain()
        if evs["lock"] and evs["proc"] and len(evs["disp"]) >= 2:
            break
        time.sleep(0.02)
    assert [e.type for e in evs["lock"]] == ["PUT"]
    assert [e.type for e in evs["proc"]] == ["PUT"]
    assert [e.type for e in evs["disp"]] == ["PUT", "DELETE"]


def test_get_many(remote):
    _, s, _ = remote
    s.put("/gm/a", "1")
    s.put("/gm/b", "2")
    out = s.get_many(["/gm/a", "/gm/missing", "/gm/b"])
    assert out[0].value == "1" and out[1] is None and out[2].value == "2"
    assert out[0].mod_rev > 0


def test_watch_delete_only_filter(remote):
    """events="delete" suppresses PUT pushes server-side (both in the
    live stream and the start_rev replay) — the scheduler watches the
    dispatch prefix it bulk-writes itself, and must not get its tens of
    thousands of own puts per window pushed back at it."""
    _, s, aux = remote
    r0 = s.put("/do/seed", "0")
    w = s.watch("/do/", events="delete")
    aux.put("/do/a", "1")
    aux.put("/do/b", "2")
    aux.delete("/do/a")
    evs = []
    deadline = time.time() + 3
    while time.time() < deadline and len(evs) < 1:
        ev = w.get(timeout=0.2)
        if ev:
            evs.append(ev)
    time.sleep(0.3)
    evs += w.drain()
    assert [(e.kv.key, e.type) for e in evs] == [("/do/a", "DELETE")]
    w.close()
    # replay path: puts filtered there too
    aux.delete("/do/b")
    w2 = s.watch("/do/", start_rev=r0, events="delete")
    evs2 = []
    deadline = time.time() + 3
    while time.time() < deadline and len(evs2) < 2:
        ev = w2.get(timeout=0.2)
        if ev:
            evs2.append(ev)
    assert [e.type for e in evs2] == ["DELETE", "DELETE"]
    assert {e.kv.key for e in evs2} == {"/do/a", "/do/b"}
    w2.close()


def test_get_prefix_paged(remote):
    """Paged prefix listing (both backends): bounded pages, key order,
    exact coverage, and resumption strictly after the cursor."""
    _, s, _ = remote
    items = [(f"/pg/{i:04d}", str(i)) for i in range(257)]
    s.put_many(items)
    s.put("/pgx", "outside")
    page = s.get_prefix_page("/pg/", "", 100)
    assert [kv.key for kv in page] == [k for k, _ in items[:100]]
    page2 = s.get_prefix_page("/pg/", page[-1].key, 100)
    assert page2[0].key == "/pg/0100"
    everything = list(s.get_prefix_paged("/pg/", page=64))
    assert [kv.key for kv in everything] == [k for k, _ in items]
    assert all(kv.value == kv.key[-4:].lstrip("0") or kv.value == "0"
               for kv in everything)


def test_get_prefix_paged_falls_back_on_old_server(monkeypatch):
    """Rolling-upgrade compatibility: against a server predating
    get_prefix_page, the paged iterator silently degrades to the
    one-shot listing instead of erroring."""
    import cronsun_tpu.store.remote as remote_mod
    monkeypatch.setattr(
        remote_mod, "_OPS",
        tuple(o for o in remote_mod._OPS if o != "get_prefix_page"))
    srv = StoreServer(MemStore()).start()
    s = RemoteStore(srv.host, srv.port)
    try:
        s.put_many([(f"/old/{i:03d}", str(i)) for i in range(120)])
        keys = [kv.key for kv in s.get_prefix_paged("/old/", page=50)]
        assert keys == [f"/old/{i:03d}" for i in range(120)]
    finally:
        s.close()
        srv.stop()


def test_find_binary_builds_once_under_contention(tmp_path, monkeypatch):
    """Two callers that both find the binary stale (xdist workers, a
    `bin.store --native` beside them) must not both run `make`, and
    neither may get the path back while the other is still linking."""
    import os
    import subprocess
    import threading

    from cronsun_tpu import native_launcher

    (tmp_path / "foo.cc").write_text("int main() {}\n")
    cand = tmp_path / "cronsun-foo"
    makes = []

    def fake_make(argv, **kw):
        makes.append(argv)
        time.sleep(0.3)            # a link in progress
        cand.write_text("#!/bin/sh\n")
        cand.chmod(0o755)
        return subprocess.CompletedProcess(argv, 0)

    monkeypatch.setattr(native_launcher, "NATIVE_DIR", tmp_path)
    monkeypatch.setattr(native_launcher.subprocess, "run", fake_make)
    monkeypatch.delenv("CRONSUN_FOO", raising=False)
    got = []
    threads = [threading.Thread(target=lambda: got.append(
        (native_launcher.find_binary("cronsun-foo", "CRONSUN_FOO"),
         os.access(cand, os.X_OK)))) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert got == [(str(cand), True)] * 2
    assert len(makes) == 1, makes
