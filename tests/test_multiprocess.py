"""The deployment story, end-to-end: store + scheduler + 2 agents + web
as SEPARATE OS processes (the reference's N-machines-against-etcd
topology, bin/node/server.go:23-70, bin/web/server.go:24-88).

A job is created through the REST API, planned by the scheduler process,
executed by both agent processes, and its results land in the NETWORKED
result store (cronsun-logd — the rebuild's Mongo) — all plumbing
crossing real process boundaries over TCP, with no shared filesystem
between any two processes.
"""

import http.cookiejar
import json
import os
import signal
import subprocess
import sys
import time
import urllib.parse
import urllib.request

import pytest

from cronsun_tpu.logsink import JobLogStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _spawn(mod, *args, env=None):
    e = dict(os.environ)
    e["JAX_PLATFORMS"] = "cpu"
    e["PYTHONPATH"] = REPO
    e.update(env or {})
    return subprocess.Popen(
        [sys.executable, "-m", mod, *args], cwd=REPO, env=e,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)


def _await_ready(proc, timeout=90):
    deadline = time.time() + timeout
    lines = []
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            if proc.poll() is not None:
                raise AssertionError(
                    f"process died rc={proc.returncode}:\n{''.join(lines)}")
            continue
        lines.append(line)
        if line.startswith("READY"):
            # keep draining (discarding) forever: an undrained 64KB
            # pipe blocks the process mid-log-line — a scheduler
            # printing reconnect errors through a store outage would
            # WEDGE on the full pipe and never resume dispatching
            # (exactly the failure the crash tests then misreport)
            import threading
            keep = os.environ.get("TEST_KEEP_LOGS")

            def _drain(f=proc.stdout, pid=proc.pid):
                if keep:
                    with open(f"{keep}/{pid}.log", "w") as out:
                        for ln in f:
                            out.write(ln)
                            out.flush()
                else:
                    for _ in f:
                        pass
            threading.Thread(target=_drain, daemon=True).start()
            return line.split(None, 1)[1].strip()
    raise AssertionError(f"no READY within {timeout}s:\n{''.join(lines)}")


def _login(web_addr):
    """Cookie-authenticated opener against a fleet's web process."""
    cj = http.cookiejar.CookieJar()
    op = urllib.request.build_opener(urllib.request.HTTPCookieProcessor(cj))
    base = f"http://{web_addr}"
    q = urllib.parse.urlencode(
        {"email": "admin@admin.com", "password": "admin"})
    with op.open(f"{base}/v1/session?{q}", timeout=10) as r:
        assert r.status == 200
    return op, base


def _put_job(op, base, job):
    req = urllib.request.Request(
        f"{base}/v1/job", data=json.dumps(job).encode(), method="PUT",
        headers={"Content-Type": "application/json"})
    with op.open(req, timeout=10) as r:
        assert r.status == 200


def _teardown(procs):
    for p in procs:
        if p.poll() is None:
            p.send_signal(signal.SIGTERM)
    for p in procs:
        try:
            p.wait(timeout=10)
        except subprocess.TimeoutExpired:
            p.kill()


@pytest.mark.parametrize("store_backend", ["py", "native"])
def test_full_system_multiprocess(tmp_path, store_backend):
    if store_backend == "native":
        from cronsun_tpu.store.native import find_binary
        if find_binary() is None:
            pytest.skip("native store binary unavailable")
    # every process gets a DIFFERENT local log_db path; none may be
    # touched — results flow only through the logd process (the
    # reference's networked Mongo, db/mgo.go:24-49)
    conf = tmp_path / "conf.json"
    conf.write_text(json.dumps({
        "log_db": str(tmp_path / "local-UNUSED.db"), "window_s": 2,
        "node_ttl": 5, "job_capacity": 256, "node_capacity": 64,
        "proc_req": 0}))

    procs = []
    try:
        store_args = ["--port", "0"]
        logd_args = ["--port", "0", "--db", str(tmp_path / "logd.db")]
        if store_backend == "native":
            # the all-native fleet: C++ coordination store AND C++
            # result store behind the same Python clients
            store_args.append("--native")
            logd_args.append("--native")
        store_p = _spawn("cronsun_tpu.bin.store", *store_args)
        procs.append(store_p)
        store_addr = _await_ready(store_p)
        logd_p = _spawn("cronsun_tpu.bin.logd", *logd_args)
        procs.append(logd_p)
        logd_addr = _await_ready(logd_p)

        sched_p = _spawn("cronsun_tpu.bin.sched", "--store", store_addr,
                         "--conf", str(conf))
        procs.append(sched_p)
        node_ps = [
            _spawn("cronsun_tpu.bin.node", "--store", store_addr,
                   "--logsink", logd_addr,
                   "--conf", str(conf), "--node-id", f"mp-node-{i}")
            for i in range(2)]
        procs += node_ps
        web_p = _spawn("cronsun_tpu.bin.web", "--store", store_addr,
                       "--logsink", logd_addr,
                       "--conf", str(conf), "--port", "0")
        procs.append(web_p)

        _await_ready(sched_p)
        for p in node_ps:
            _await_ready(p)
        web_addr = _await_ready(web_p)

        # -- drive through the REST API (cookie session auth) -------------
        op, base = _login(web_addr)

        job = {"name": "mp-hello", "command": "echo multiproc", "kind": 0,
               "group": "default",
               "rules": [{"timer": "* * * * * *",
                          "nids": ["mp-node-0", "mp-node-1"]}]}
        _put_job(op, base, job)

        with op.open(f"{base}/v1/nodes", timeout=10) as r:
            nodes = json.loads(r.read())
        connected = {n["id"] for n in nodes if n.get("connected")}
        assert {"mp-node-0", "mp-node-1"} <= connected

        # -- wait for cross-process executions to land in logd ------------
        from cronsun_tpu.logsink import RemoteJobLogStore
        lh, _, lp = logd_addr.rpartition(":")
        sink = RemoteJobLogStore(lh, int(lp))
        deadline = time.time() + 60
        seen = set()
        while time.time() < deadline:
            logs, total = sink.query_logs()
            seen = {l.node for l in logs}
            if total >= 4 and seen >= {"mp-node-0", "mp-node-1"}:
                break
            time.sleep(1)
        logs, total = sink.query_logs()
        assert total >= 4, f"only {total} executions landed"
        assert {l.node for l in logs} >= {"mp-node-0", "mp-node-1"}
        assert all(l.success for l in logs)
        assert all("multiproc" in l.output for l in logs)

        # REST view of the same results — the web process reads them over
        # the wire, no shared file with the agents
        with op.open(f"{base}/v1/logs", timeout=10) as r:
            api_logs = json.loads(r.read())
        assert api_logs["total"] >= 4
        sink.close()
        # nothing fell back to the local SQLite path
        assert not os.path.exists(str(tmp_path / "local-UNUSED.db")), \
            "a process wrote the local log_db despite --logsink"

        # the operator metrics surface sees the scheduler process's
        # published snapshot (planner ticks are non-zero)
        with op.open(f"{base}/v1/metrics", timeout=10) as r:
            metrics = r.read().decode()
        import re as _re
        m = _re.search(r'cronsun_sched_steps_total\{[^}]*\} (\d+)', metrics)
        assert m and int(m.group(1)) > 0, \
            f"no planner ticks visible in /v1/metrics:\n{metrics}"
        assert "cronsun_sched_tick_p99_ms" in metrics
    finally:
        _teardown(procs)


def test_node_crash_alert_across_processes(tmp_path):
    """The noticer's crash detection depends on a SHARED node mirror
    (reference noticer.go:172-200 checks Mongo's alived flag): with the
    mirror in logd, a SIGKILLed agent in one process tree produces a
    node-down alert from the web process in another — no shared
    filesystem anywhere."""
    import http.server
    import threading

    alerts = []

    class Recv(http.server.BaseHTTPRequestHandler):
        def do_POST(self):
            body = self.rfile.read(int(self.headers.get("Content-Length",
                                                        0)))
            alerts.append(json.loads(body))
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    recv = http.server.HTTPServer(("127.0.0.1", 0), Recv)
    threading.Thread(target=recv.serve_forever, daemon=True).start()

    conf = tmp_path / "conf.json"
    conf.write_text(json.dumps({
        "log_db": str(tmp_path / "local-UNUSED.db"), "window_s": 2,
        "node_ttl": 3, "proc_req": 0,
        "mail": {"enable": True,
                 "http_api": f"http://127.0.0.1:{recv.server_port}/"}}))

    procs = []
    try:
        store_p = _spawn("cronsun_tpu.bin.store", "--port", "0")
        procs.append(store_p)
        store_addr = _await_ready(store_p)
        logd_p = _spawn("cronsun_tpu.bin.logd", "--port", "0",
                        "--db", str(tmp_path / "logd.db"))
        procs.append(logd_p)
        logd_addr = _await_ready(logd_p)

        node_p = _spawn("cronsun_tpu.bin.node", "--store", store_addr,
                        "--logsink", logd_addr, "--conf", str(conf),
                        "--node-id", "doomed-node")
        procs.append(node_p)
        web_p = _spawn("cronsun_tpu.bin.web", "--store", store_addr,
                       "--logsink", logd_addr, "--conf", str(conf),
                       "--port", "0")
        procs.append(web_p)
        _await_ready(node_p)
        _await_ready(web_p)

        # agent registered: mirror (in logd) says alive
        from cronsun_tpu.logsink import RemoteJobLogStore
        lh, _, lp = logd_addr.rpartition(":")
        sink = RemoteJobLogStore(lh, int(lp))
        deadline = time.time() + 20
        while time.time() < deadline:
            n = sink.get_node("doomed-node")
            if n and n.get("alived"):
                break
            time.sleep(0.2)
        assert sink.get_node("doomed-node")["alived"]

        node_p.send_signal(signal.SIGKILL)        # crash, not clean stop
        node_p.wait(timeout=10)

        # lease (ttl+2) expires -> web's noticer alerts via HTTP API
        deadline = time.time() + 30
        while time.time() < deadline and not alerts:
            time.sleep(0.5)
        assert alerts, "no crash alert crossed the process boundary"
        assert "doomed-node" in alerts[0]["subject"]
        # delivered alert flips the shared mirror to dead
        deadline = time.time() + 10
        while time.time() < deadline and \
                sink.get_node("doomed-node")["alived"]:
            time.sleep(0.2)
        assert not sink.get_node("doomed-node")["alived"]
        sink.close()
    finally:
        recv.shutdown()
        _teardown(procs)


def test_secured_fleet_end_to_end(tmp_path):
    """A token-secured deployment: native store and logd both require
    their shared secrets; correctly-configured processes execute a job
    end to end while tokenless/wrong-token clients are refused."""
    from cronsun_tpu.store.native import find_binary
    if find_binary() is None:
        pytest.skip("native store binary unavailable")
    conf = tmp_path / "conf.json"
    conf.write_text(json.dumps({
        "log_db": str(tmp_path / "local-UNUSED.db"), "window_s": 2,
        "node_ttl": 5, "proc_req": 0,
        "store_token": "st-secret", "log_token": "lg-secret"}))

    procs = []
    try:
        store_p = _spawn("cronsun_tpu.bin.store", "--native", "--port", "0",
                         "--token", "st-secret")
        procs.append(store_p)
        store_addr = _await_ready(store_p)
        logd_p = _spawn("cronsun_tpu.bin.logd", "--port", "0",
                        "--db", str(tmp_path / "logd.db"),
                        "--token", "lg-secret")
        procs.append(logd_p)
        logd_addr = _await_ready(logd_p)

        # wrong/missing tokens are refused before any op
        from cronsun_tpu.logsink import LogSinkError, RemoteJobLogStore
        from cronsun_tpu.store.remote import RemoteStore, RemoteStoreError
        sh, _, sp = store_addr.rpartition(":")
        bad = RemoteStore(sh, int(sp), reconnect=False)
        with pytest.raises(RemoteStoreError):
            bad.put("/x", "1")
        bad.close()
        lh, _, lp = logd_addr.rpartition(":")
        with pytest.raises(LogSinkError):
            RemoteJobLogStore(lh, int(lp), token="wrong")

        sched_p = _spawn("cronsun_tpu.bin.sched", "--store", store_addr,
                         "--conf", str(conf))
        node_p = _spawn("cronsun_tpu.bin.node", "--store", store_addr,
                        "--logsink", logd_addr, "--conf", str(conf),
                        "--node-id", "sec-node")
        web_p = _spawn("cronsun_tpu.bin.web", "--store", store_addr,
                       "--logsink", logd_addr, "--conf", str(conf),
                       "--port", "0")
        procs += [sched_p, node_p, web_p]
        _await_ready(sched_p)
        _await_ready(node_p)
        web_addr = _await_ready(web_p)

        op, base = _login(web_addr)
        nids = ["sec-node"]
        job = {"name": "sec", "command": "echo secured", "kind": 0,
               "rules": [{"timer": "* * * * * *", "nids": nids}]}
        _put_job(op, base, job)

        sink = RemoteJobLogStore(lh, int(lp), token="lg-secret")
        deadline = time.time() + 45
        nodes_seen = set()
        while time.time() < deadline and nodes_seen != set(nids):
            logs, total = sink.query_logs(page_size=200)
            nodes_seen = {l.node for l in logs}
            time.sleep(0.5)
        assert nodes_seen == set(nids), \
            f"secured fleet missing executions from {set(nids) - nodes_seen}"
        sink.close()
    finally:
        _teardown(procs)


def test_logd_crash_restart_fleet_heals(tmp_path):
    """The result store (cronsun-logd) is SIGKILLed mid-run and
    restarted on the same port with the same SQLite file: agents heal
    their connections (one transparent retry + reconnect), no execution
    record is double-counted (idempotency tokens), and history from
    before the crash survives."""
    import socket as _socket
    from cronsun_tpu.logsink import RemoteJobLogStore

    sock = _socket.socket()
    sock.bind(("127.0.0.1", 0))
    logd_port = sock.getsockname()[1]
    sock.close()
    logd_db = str(tmp_path / "logd.db")
    conf = tmp_path / "conf.json"
    conf.write_text(json.dumps({
        "log_db": str(tmp_path / "local-UNUSED.db"), "window_s": 2,
        "node_ttl": 5, "proc_req": 0}))

    def spawn_logd():
        p = _spawn("cronsun_tpu.bin.logd", "--port", str(logd_port),
                   "--db", logd_db)
        procs.append(p)       # registered BEFORE awaiting: a wedged
        _await_ready(p)       # start must still be torn down
        return p

    procs = []
    logd_p = None
    try:
        store_p = _spawn("cronsun_tpu.bin.store", "--port", "0")
        procs.append(store_p)
        store_addr = _await_ready(store_p)
        logd_p = spawn_logd()
        logd_addr = f"127.0.0.1:{logd_port}"

        sched_p = _spawn("cronsun_tpu.bin.sched", "--store", store_addr,
                         "--conf", str(conf))
        node_p = _spawn("cronsun_tpu.bin.node", "--store", store_addr,
                        "--logsink", logd_addr, "--conf", str(conf),
                        "--node-id", "ld-node")
        web_p = _spawn("cronsun_tpu.bin.web", "--store", store_addr,
                       "--logsink", logd_addr, "--conf", str(conf),
                       "--port", "0")
        procs += [sched_p, node_p, web_p]
        _await_ready(sched_p)
        _await_ready(node_p)
        web_addr = _await_ready(web_p)

        op, base = _login(web_addr)
        job = {"name": "ld", "command": "echo heal-logd", "kind": 0,
               "rules": [{"timer": "* * * * * *", "nids": ["ld-node"]}]}
        _put_job(op, base, job)

        def count():
            c = RemoteJobLogStore("127.0.0.1", logd_port)
            try:
                _, n = c.query_logs()
                return n
            finally:
                c.close()

        deadline = time.time() + 45
        while time.time() < deadline and count() < 3:
            time.sleep(0.5)
        before = count()
        assert before >= 3, f"no executions before logd crash ({before})"

        logd_p.send_signal(signal.SIGKILL)
        logd_p.wait(timeout=10)
        time.sleep(2)                       # agents hit the dead sink
        logd_p = spawn_logd()

        deadline = time.time() + 60
        while time.time() < deadline and count() < before + 3:
            time.sleep(0.5)
        after = count()
        assert after >= before + 3, \
            f"executions did not resume after logd restart " \
            f"({before} -> {after})"
        # history from before the crash survived in the SQLite file
        c = RemoteJobLogStore("127.0.0.1", logd_port)
        logs, _ = c.query_logs(page_size=500)
        assert all("heal-logd" in l.output for l in logs)
        c.close()
        # no fleet process died over the outage (the first logd was
        # deliberately SIGKILLed, so it is excluded)
        for p in (store_p, sched_p, node_p, web_p):
            assert p.poll() is None, "a fleet process died with logd"
    finally:
        _teardown(procs)


def test_store_crash_restart_fleet_heals(tmp_path):
    """The deployment resilience story: the native store (with WAL) is
    killed -9 mid-flight and restarted on the same port; every client
    (scheduler, agent, web) heals its connection, the job definitions
    come back from the WAL, and executions resume."""
    from cronsun_tpu.store.native import find_binary
    if find_binary() is None:
        pytest.skip("native store binary unavailable")
    import socket as _socket
    from cronsun_tpu.logsink import JobLogStore

    sock = _socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()
    logdb = str(tmp_path / "logs.db")
    wal = str(tmp_path / "store.wal")
    conf = tmp_path / "conf.json"
    conf.write_text(json.dumps({
        "log_db": logdb, "window_s": 2, "node_ttl": 5,
        "job_capacity": 256, "node_capacity": 64, "proc_req": 0}))

    def spawn_store():
        p = _spawn("cronsun_tpu.bin.store", "--native", "--wal", wal,
                   "--port", str(port))
        _await_ready(p)
        return p

    procs = []
    try:
        store_p = spawn_store()
        sched_p = _spawn("cronsun_tpu.bin.sched", "--store",
                         f"127.0.0.1:{port}", "--conf", str(conf))
        node_p = _spawn("cronsun_tpu.bin.node", "--store",
                        f"127.0.0.1:{port}", "--conf", str(conf),
                        "--node-id", "hz-node")
        web_p = _spawn("cronsun_tpu.bin.web", "--store",
                       f"127.0.0.1:{port}", "--conf", str(conf),
                       "--port", "0")
        procs = [sched_p, node_p, web_p]
        _await_ready(sched_p)
        _await_ready(node_p)
        web_addr = _await_ready(web_p)

        op, base = _login(web_addr)
        job = {"name": "hz", "command": "echo heal", "kind": 0,
               "rules": [{"timer": "* * * * * *", "nids": ["hz-node"]}]}
        _put_job(op, base, job)

        sink = JobLogStore(logdb)

        def count():
            _, n = sink.query_logs()
            return n

        deadline = time.time() + 45
        while time.time() < deadline and count() < 3:
            time.sleep(0.5)
        before = count()
        assert before >= 3, f"no executions before crash ({before})"

        # kill -9: wrapper exits via its child monitor
        store_p.send_signal(signal.SIGKILL)
        store_p.wait(timeout=10)
        time.sleep(1)
        store_p = spawn_store()

        # executions must RESUME (strictly grow past pre-crash count)
        deadline = time.time() + 60
        while time.time() < deadline and count() < before + 3:
            time.sleep(0.5)
        after = count()
        assert after >= before + 3, \
            f"executions did not resume after store restart " \
            f"({before} -> {after})"
        # the job survived in the restarted store
        with op.open(f"{base}/v1/jobs", timeout=10) as r:
            jobs = json.loads(r.read())
        assert any(j["name"] == "hz" for j in jobs)
        sink.close()
    finally:
        procs.append(store_p)
        _teardown(procs)


def test_sched_failover_across_processes(tmp_path):
    """Two scheduler PROCESSES elect one leader; SIGKILL it mid-flight.
    The standby must take over within the leader lease TTL and planning
    must continue — executions keep landing, and the (job, second)
    fence + HWM continuity mean no second ever executes twice (the
    in-process version of this contract lives in test_integration;
    this is the real-OS-process deployment story)."""
    from cronsun_tpu.core import Keyspace
    from cronsun_tpu.core.models import Job, JobRule
    from cronsun_tpu.store.remote import RemoteStore

    log_db = str(tmp_path / "logs.db")
    conf = tmp_path / "conf.json"
    conf.write_text(json.dumps(
        {"log_db": log_db, "window_s": 2, "node_ttl": 5}))
    procs, scheds = [], {}
    try:
        store_p = _spawn("cronsun_tpu.bin.store", "--port", "0")
        procs.append(store_p)
        addr = _await_ready(store_p)
        for sid in ("sched-a", "sched-b"):
            p = _spawn("cronsun_tpu.bin.sched", "--store", addr,
                       "--conf", str(conf), "--node-id", sid)
            procs.append(p)
            scheds[sid] = p
            _await_ready(p)
        node_p = _spawn("cronsun_tpu.bin.node", "--store", addr,
                        "--conf", str(conf), "--node-id", "w1")
        procs.append(node_p)
        _await_ready(node_p)

        host, _, port = addr.rpartition(":")
        ks = Keyspace()
        c = RemoteStore(host, int(port))
        # the command echoes the second it was scheduled FOR (the agent's
        # cron-context env) — begin_ts is when it actually ran, and on a
        # loaded box late orders bunch into the same wall second, so
        # exactly-once must key on the scheduled second
        job = Job(id="fo1", group="g", name="failover-job",
                  command="sh -c 'echo $CRONSUN_SCHEDULED_TS'", kind=0,
                  rules=[JobRule(id="r1", timer="* * * * * *",
                                 nids=["w1"])])
        c.put(ks.job_key("g", "fo1"), job.to_json())

        sink = JobLogStore(log_db)

        def records():
            recs, total = sink.query_logs(page_size=500)
            return recs, total

        deadline = time.time() + 60
        while time.time() < deadline and records()[1] < 3:
            time.sleep(0.5)
        assert records()[1] >= 3, "no executions before failover"

        leader_kv = c.get(ks.leader)
        assert leader_kv is not None and leader_kv.value in scheds
        old_leader = leader_kv.value
        scheds[old_leader].send_signal(signal.SIGKILL)
        kill_ts = time.time()

        # standby takes over within the leader lease TTL (10 s default)
        deadline = time.time() + 45
        post = 0
        while time.time() < deadline:
            recs, _ = records()
            post = sum(1 for r in recs if r.begin_ts > kill_ts + 1)
            if post >= 3:
                break
            time.sleep(0.5)
        assert post >= 3, "executions never resumed after leader death"
        new_leader = c.get(ks.leader)
        assert new_leader is not None and new_leader.value != old_leader

        # exactly-once held across the failover: one record per SCHEDULED
        # second on the single eligible node (the HWM keeps the new
        # leader from re-dispatching seconds the dead one already did)
        recs, _ = records()
        scheduled = [r.output.strip() for r in recs]
        assert all(s.isdigit() for s in scheduled), scheduled
        assert len(scheduled) == len(set(scheduled)), \
            "a scheduled second executed twice across the failover"
        # HWM continuity bound (VERDICT r3 #3): the takeover gap stayed
        # under max_catchup_s — the new leader resumed from the HWM and
        # planned every second late rather than skipping any (its
        # skipped_seconds metric is 0), and the observed gap between
        # consecutive SCHEDULED seconds is far below the catch-up limit.
        secs = sorted(int(s) for s in scheduled)
        max_gap = max((b - a for a, b in zip(secs, secs[1:])), default=0)
        assert max_gap <= 120, f"scheduled-second gap {max_gap}s breached " \
                               f"max_catchup_s across the failover"
        snap_kv = c.get(ks.metrics_key("sched", new_leader.value))
        assert snap_kv is not None
        snap = json.loads(snap_kv.value)
        assert snap.get("skipped_seconds_total", 0) == 0, snap
        c.close()
        sink.close()
    finally:
        _teardown(procs)


def test_tls_fleet_end_to_end(tmp_path):
    """A TLS-secured deployment as real OS processes: Python store and
    logd terminate TLS (certs from scripts/gen_certs.sh), every client
    process carries the fleet CA in its conf, tokens ride inside the
    encrypted channel, and a job executes end to end.  The refusal
    matrix lives in tests/test_tls.py; this pins the full-fleet wiring
    (conf sections -> entrypoints -> both wires)."""
    certs = tmp_path / "certs"
    subprocess.run(["sh", "scripts/gen_certs.sh", str(certs)], check=True,
                   capture_output=True, cwd=REPO)
    # one shared section per channel works for servers AND clients:
    # servers read cert/key, clients read ca/hostname (client_ca —
    # mutual TLS — stays a deliberate, separate server knob)
    conf = tmp_path / "conf.json"
    conf.write_text(json.dumps({
        "log_db": str(tmp_path / "local-UNUSED.db"), "window_s": 2,
        "node_ttl": 5, "store_token": "st", "log_token": "lg",
        "store_tls": {"ca": str(certs / "ca.pem"),
                      "cert": str(certs / "server.pem"),
                      "key": str(certs / "server.key"),
                      "hostname": "localhost"},
        "log_tls": {"ca": str(certs / "ca.pem"),
                    "cert": str(certs / "server.pem"),
                    "key": str(certs / "server.key"),
                    "hostname": "localhost"}}))

    procs = []
    try:
        store_p = _spawn("cronsun_tpu.bin.store", "--port", "0",
                         "--conf", str(conf))
        procs.append(store_p)
        store_addr = _await_ready(store_p)
        logd_p = _spawn("cronsun_tpu.bin.logd", "--port", "0",
                        "--db", str(tmp_path / "logd.db"),
                        "--conf", str(conf))
        procs.append(logd_p)
        logd_addr = _await_ready(logd_p)

        sched_p = _spawn("cronsun_tpu.bin.sched", "--store", store_addr,
                         "--conf", str(conf))
        node_p = _spawn("cronsun_tpu.bin.node", "--store", store_addr,
                        "--logsink", logd_addr, "--conf", str(conf),
                        "--node-id", "tls-node")
        web_p = _spawn("cronsun_tpu.bin.web", "--store", store_addr,
                       "--logsink", logd_addr, "--conf", str(conf),
                       "--port", "0")
        procs += [sched_p, node_p, web_p]
        _await_ready(sched_p)
        _await_ready(node_p)
        web_addr = _await_ready(web_p)

        # a plaintext client cannot reach the TLS store
        from cronsun_tpu.store.remote import RemoteStore, RemoteStoreError
        sh_, _, sp_ = store_addr.rpartition(":")
        with pytest.raises((RemoteStoreError, OSError)):
            plain = RemoteStore(sh_, int(sp_), reconnect=False, timeout=3)
            plain.put("/x", "1")

        op, base = _login(web_addr)
        _put_job(op, base, {
            "name": "tls-fleet", "command": "echo over-tls", "kind": 0,
            "rules": [{"timer": "* * * * * *", "nids": ["tls-node"]}]})

        from cronsun_tpu.logsink import RemoteJobLogStore
        from cronsun_tpu.tlsutil import Tls, client_context
        lh, _, lp = logd_addr.rpartition(":")
        sink = RemoteJobLogStore(
            lh, int(lp), token="lg",
            sslctx=client_context(Tls(ca=str(certs / "ca.pem"),
                                      hostname="localhost")),
            tls_hostname="localhost")
        deadline = time.time() + 45
        total = 0
        while time.time() < deadline and total < 2:
            logs, total = sink.query_logs(page_size=50)
            time.sleep(0.5)
        assert total >= 2, "no executions landed through the TLS fleet"
        assert all("over-tls" in l.output for l in logs)
        sink.close()
    finally:
        _teardown(procs)
