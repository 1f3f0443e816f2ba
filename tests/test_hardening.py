"""Round-4 hardening regressions (ADVICE items).

- mesh-worker signal watchdog: first TERM/INT ignored, second (or one
  SIGUSR1) force-exits — even with the main thread parked in a C-level
  blocking call that SA_RESTART restarts (the wedged-collective
  analogue; bin/sched.py install_worker_signal_watchdog).
- wire handshake deadline: an unauthenticated connection — silent OR
  drip-feeding bytes — is severed at the wall-clock deadline; an authed
  client outlives it (store/wire.py HANDSHAKE_TIMEOUT watchdog).
- web: POST /v1/session (body creds), 400 on malformed query ints, 400
  on a valid-JSON-non-object login body.
- hostsync proxy: un-logged planner mutators fail loudly.
"""

import json
import socket
import subprocess
import sys
import time

import pytest

from cronsun_tpu.store.remote import RemoteStore, StoreServer
from cronsun_tpu.store import wire


_WD_SCRIPT = r"""
import os, sys
sys.path.insert(0, {repo!r})
from cronsun_tpu.bin.sched import install_worker_signal_watchdog
install_worker_signal_watchdog()
print("WD READY", flush=True)
r, _w = os.pipe()
os.read(r, 1)   # parked in C; SA_RESTART restarts it across signals
"""


def _spawn_watchdog_proc(tmp_path):
    import os
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    p = subprocess.Popen([sys.executable, "-c",
                          _WD_SCRIPT.format(repo=repo)],
                         stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
                         text=True)
    assert "WD READY" in p.stdout.readline()
    time.sleep(0.2)
    return p


@pytest.mark.parametrize("sig", ["TERM", "INT"])
def test_watchdog_second_signal_force_exits(tmp_path, sig):
    import signal
    signum = getattr(signal, f"SIG{sig}")
    p = _spawn_watchdog_proc(tmp_path)
    p.send_signal(signum)
    time.sleep(0.5)
    assert p.poll() is None, "first signal must be ignored"
    p.send_signal(signum)
    assert p.wait(timeout=5) == 1
    out = p.stdout.read()
    assert "first signal ignored" in out and "force exit" in out


def test_watchdog_sigusr1_immediate(tmp_path):
    import signal
    p = _spawn_watchdog_proc(tmp_path)
    p.send_signal(signal.SIGUSR1)
    assert p.wait(timeout=5) == 1
    assert "force exit" in p.stdout.read()


@pytest.fixture
def fast_handshake(monkeypatch):
    monkeypatch.setattr(wire.LineJsonHandler, "HANDSHAKE_TIMEOUT", 1.0)


def test_unauthed_silent_conn_severed(fast_handshake):
    srv = StoreServer(token="t0k").start()
    try:
        s = socket.create_connection((srv.host, srv.port))
        s.settimeout(5)
        t0 = time.time()
        assert s.recv(1) == b""          # server severs; EOF
        assert 0.5 < time.time() - t0 < 3
    finally:
        srv.stop()


def test_unauthed_dripfeed_severed(fast_handshake):
    """Partial progress must not extend the deadline (absolute, not
    per-recv)."""
    srv = StoreServer(token="t0k").start()
    try:
        s = socket.create_connection((srv.host, srv.port))
        s.settimeout(5)
        t0 = time.time()
        dead = None
        for _ in range(12):              # a byte every 0.3s, no newline
            try:
                s.sendall(b"x")
            except OSError:
                break
            time.sleep(0.3)
        s.settimeout(2)
        try:
            if s.recv(1) == b"":
                dead = time.time() - t0
        except OSError:
            dead = time.time() - t0
        assert dead is not None and dead < 4
    finally:
        srv.stop()


def test_authed_client_outlives_deadline(fast_handshake):
    srv = StoreServer(token="t0k").start()
    try:
        c = RemoteStore(srv.host, srv.port, token="t0k", reconnect=False)
        c.put("/hp/k", "v")
        time.sleep(1.5)                  # idle past the deadline
        assert c.get("/hp/k").value == "v"
        c.close()
    finally:
        srv.stop()


# ---- web: POST login + 400s ------------------------------------------------

@pytest.fixture
def web():
    from cronsun_tpu.logsink import JobLogStore
    from cronsun_tpu.store.memstore import MemStore
    from cronsun_tpu.web import ApiServer
    store = MemStore()
    sink = JobLogStore(":memory:")
    srv = ApiServer(store, sink, host="127.0.0.1", port=0).start()
    yield srv
    srv.stop()


def _req(srv, method, path, body=None, cookie=""):
    import urllib.request
    import urllib.error
    headers = {"Content-Type": "application/json"}
    if cookie:
        headers["Cookie"] = cookie
    req = urllib.request.Request(
        f"http://127.0.0.1:{srv.port}{path}",
        data=json.dumps(body).encode() if body is not None else None,
        method=method, headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=10) as r:
            return (r.status, json.loads(r.read() or b"null"),
                    r.headers.get("Set-Cookie", ""))
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"null"), ""


def test_post_login_and_malformed_ints(web):
    code, out, setc = _req(web, "POST", "/v1/session",
                           {"email": "admin@admin.com", "password": "admin"})
    assert code == 200 and out["email"] == "admin@admin.com"
    sid = setc.split(";")[0]
    code, _, _ = _req(web, "POST", "/v1/session",
                      {"email": "admin@admin.com", "password": "nope"})
    assert code == 401
    code, _, _ = _req(web, "POST", "/v1/session", "not-a-dict")
    assert code == 400
    code, out, _ = _req(web, "GET", "/v1/logs?afterId=xyz", cookie=sid)
    assert code == 400 and "afterId" in out["error"]
    code, _, _ = _req(web, "GET", "/v1/logs?page=1&pageSize=5", cookie=sid)
    assert code == 200


def test_hostsync_unlogged_mutator_raises():
    from cronsun_tpu.parallel.hostsync import PlannerSyncProxy

    class _P:
        N = 4

    proxy = PlannerSyncProxy(_P())
    assert proxy.N == 4                      # reads pass through
    with pytest.raises(RuntimeError, match="op-log"):
        proxy.set_table
    with pytest.raises(RuntimeError, match="op-log"):
        proxy.decay_load


def test_sched_names_its_device_and_first_window_impl():
    """bin.sched's line before READY: the device as JAX reports it and
    the kernel variant the planner resolves for its first window — on
    this CPU backend choose_impl answers "jnp", which is exactly what
    chip_smoke.py must be able to see and refuse."""
    import jax
    from cronsun_tpu.bin.sched import describe_device
    from cronsun_tpu.ops.planner import TickPlanner
    from cronsun_tpu.parallel.mesh import ShardedTickPlanner, make_mesh
    want = {"platform": "cpu", "device_kind": jax.devices()[0].device_kind,
            "count": len(jax.devices()), "impl": "jnp"}
    assert describe_device(TickPlanner(512, 64)) == want
    assert describe_device(ShardedTickPlanner(
        make_mesh(2), job_capacity=1024, node_capacity=64)) == want
    orig = jax.default_backend
    try:
        jax.default_backend = lambda: "tpu"
        # a fresh planner's first window runs at the 65,536 bucket
        assert describe_device(TickPlanner(1 << 17, 10240))["impl"] \
            == "pallas"
        assert describe_device(TickPlanner(512, 64, max_fire_bucket=2048)
                               )["impl"] == "mixed"
    finally:
        jax.default_backend = orig

