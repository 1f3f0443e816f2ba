"""Batched watch delivery over the wire.

The store servers ship watch events as {"w": wid, "evs": [...]} batch
frames (one pump/writer per connection) instead of one line per event.
These tests pin the contract:

- a burst of K events arrives in far fewer than K frames, with at least
  one frame carrying len(evs) > 1 — on BOTH backends, at the raw wire
  level;
- the batched path loses nothing and preserves order (tier-1 smoke:
  frames/event ratio < 1 with zero event loss);
- slow-consumer overflow still surfaces the lossy-stream contract
  (a {"w", "lost": true} frame on the wire -> WatchLost client-side).
"""

import json
import socket
import time

import pytest

from cronsun_tpu.store.memstore import MemStore, WatchLost
from cronsun_tpu.store.native import NativeStoreServer, find_binary
from cronsun_tpu.store.remote import RemoteStore, StoreServer

BACKENDS = ["py", "native"]


def _make_server(backend):
    if backend == "py":
        return StoreServer(MemStore()).start()
    binary = find_binary()
    if binary is None:
        pytest.skip("native store binary unavailable")
    return NativeStoreServer(binary=binary)


class _RawWatchClient:
    """A line-level protocol client: exposes the actual frames the
    server ships, which the typed RemoteStore hides."""

    def __init__(self, host, port):
        self.sock = socket.create_connection((host, port), timeout=5)
        self.buf = b""

    def send(self, obj):
        self.sock.sendall(
            (json.dumps(obj, separators=(",", ":")) + "\n").encode())

    def frames(self, deadline_s, stop_when=None):
        out = []
        deadline = time.time() + deadline_s
        self.sock.settimeout(0.2)
        while time.time() < deadline:
            try:
                chunk = self.sock.recv(1 << 20)
            except (TimeoutError, socket.timeout):
                if stop_when and stop_when(out):
                    break
                continue
            if not chunk:
                break
            self.buf += chunk
            while b"\n" in self.buf:
                line, self.buf = self.buf.split(b"\n", 1)
                out.append(json.loads(line))
            if stop_when and stop_when(out):
                break
        return out

    def close(self):
        self.sock.close()


def _event_count(frames):
    return sum(len(f["evs"]) for f in frames if "evs" in f)


@pytest.mark.parametrize("backend", BACKENDS)
def test_burst_arrives_in_batched_frames(backend):
    """K events from one put_many burst arrive complete and in order,
    in far fewer than K wire frames, with at least one frame carrying
    len(evs) > 1."""
    srv = _make_server(backend)
    writer = RemoteStore(srv.host, srv.port)
    raw = _RawWatchClient(srv.host, srv.port)
    try:
        raw.send({"i": 1, "o": "watch", "a": ["/wb/", 0]})
        # wait for the watch reply before writing the burst
        acks = raw.frames(3, stop_when=lambda fs: any(
            f.get("i") == 1 for f in fs))
        assert any(f.get("i") == 1 and "r" in f for f in acks)
        K = 400
        writer.put_many([(f"/wb/{i:04d}", str(i)) for i in range(K)])
        frames = [f for f in raw.frames(
            5, stop_when=lambda fs: _event_count(fs) >= K) if "w" in f]
        assert _event_count(frames) == K, "event loss on the wire"
        assert len(frames) < K, \
            f"no batching: {len(frames)} frames for {K} events"
        assert any(len(f.get("evs", [])) > 1 for f in frames), \
            "burst never produced a multi-event frame"
        # order preserved across frames
        keys = [ev[1][0] for f in frames for ev in f.get("evs", [])]
        assert keys == [f"/wb/{i:04d}" for i in range(K)]
    finally:
        raw.close()
        writer.close()
        srv.stop()


@pytest.mark.parametrize("backend", BACKENDS)
def test_batched_path_active_and_lossless(backend):
    """Tier-1 smoke for the batching tentpole: a watched burst drains
    completely through the typed client (zero loss, exact order) and
    the server's op_stats show frames/event < 1 — proof the batched
    path, not the legacy line-per-event path, carried it."""
    srv = _make_server(backend)
    s = RemoteStore(srv.host, srv.port)
    try:
        w = s.watch("/smoke/")
        K = 1000
        s.put_many([(f"/smoke/{i:05d}", "x") for i in range(K)])
        got = []
        deadline = time.time() + 10
        while len(got) < K and time.time() < deadline:
            got.extend(w.drain())
            time.sleep(0.01)
        assert len(got) == K, f"lost {K - len(got)} events"
        assert [e.kv.key for e in got] == \
            [f"/smoke/{i:05d}" for i in range(K)]
        stats = s.op_stats()
        frames = stats["watch_frames"]["count"]
        events = stats["watch_events"]["count"]
        assert events >= K
        assert frames / events < 1.0, \
            f"batching inactive: {frames} frames / {events} events"
    finally:
        s.close()
        srv.stop()


def test_overflow_still_ships_lost_frame():
    """Slow-consumer cancellation survives batching: when the server
    cancels an overflowed watcher, the wire carries a {"w", "lost"}
    frame and the typed client raises WatchLost after the buffered
    tail — never a silent starve."""
    srv = StoreServer(MemStore()).start()
    s = RemoteStore(srv.host, srv.port)
    raw = _RawWatchClient(srv.host, srv.port)
    try:
        # typed client watch, shrunk server-side backlog
        w = s.watch("/ovf/")
        s.put("/ovf/seed", "0")
        assert w.get(timeout=3) is not None
        for sw in list(srv.store._watchers):
            if sw.prefix == "/ovf/":
                sw._max_backlog = 3
        # raw wire view of a second overflowing watcher
        raw.send({"i": 7, "o": "watch", "a": ["/ovf/", 0]})
        raw.frames(3, stop_when=lambda fs: any(
            f.get("i") == 7 for f in fs))
        for sw in list(srv.store._watchers):
            if sw.prefix == "/ovf/":
                sw._max_backlog = 3
        for i in range(50):
            srv.store.put(f"/ovf/{i}", "x")
        frames = raw.frames(5, stop_when=lambda fs: any(
            f.get("lost") for f in fs))
        assert any(f.get("w") == 7 and f.get("lost") for f in frames), \
            "overflow never shipped a lost frame"
        got_lost = False
        deadline = time.time() + 5
        while time.time() < deadline and not got_lost:
            try:
                w.get(timeout=0.2)
            except WatchLost:
                got_lost = True
        assert got_lost, "typed client never learned the stream was lost"
    finally:
        raw.close()
        s.close()
        srv.stop()
