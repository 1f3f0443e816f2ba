"""Config system, event bus, noticer."""

import json
import time

import pytest

from cronsun_tpu import events
from cronsun_tpu.conf import Config, ConfigWatcher, load_file, parse
from cronsun_tpu.core import Keyspace
from cronsun_tpu.logsink import JobLogStore
from cronsun_tpu.noticer import HttpNoticer, Notice, NoticerHost
from cronsun_tpu.store import MemStore

KS = Keyspace()


# -------------------------------------------------------------------- conf

def test_defaults():
    cfg = parse(None)
    assert cfg.node_ttl == 10 and cfg.lock_ttl == 300
    assert cfg.prefix == "/cronsun"


def test_extend_and_substitution(tmp_path):
    base = tmp_path / "base.json"
    base.write_text(json.dumps({"node_ttl": 30, "proc_ttl": 700,
                                "log_db": "@pwd@/x.db"}))
    child = tmp_path / "child.json"
    child.write_text(json.dumps({"@extend:": "base.json", "proc_ttl": 99}))
    cfg = parse(str(child))
    assert cfg.node_ttl == 30          # from base
    assert cfg.proc_ttl == 99          # child overrides
    assert cfg.log_db == str(tmp_path / "x.db")  # @pwd@ expanded


@pytest.mark.parametrize("env_dir", [None, "somewhere-else"])
def test_compile_cache_is_placed_from_outside(tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR set: the program sets NO directory (JAX
    reads the variable).  Unset: the one fixed path inside the checkout,
    which is also conf.compile_cache's default.  A child process, so the
    persistent cache stays off in this one."""
    import os
    import subprocess
    import sys
    from cronsun_tpu.conf import COMPILE_CACHE_DIR
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    assert parse(None).compile_cache == COMPILE_CACHE_DIR
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = COMPILE_CACHE_DIR
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from cronsun_tpu.bin.common import enable_compile_cache\n"
         "enable_compile_cache()\n"
         "print(jax.config.jax_compilation_cache_dir)"],
        cwd=repo, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == want
    # with the variable set nothing is created at the in-code path's
    # expense: the directory named from outside is JAX's to make
    assert not env_dir or not os.path.exists(want)


def test_nested_sections(tmp_path):
    p = tmp_path / "c.json"
    p.write_text(json.dumps({
        "security": {"open": True, "users": ["worker"], "exts": [".sh"]},
        "web": {"port": 8080}}))
    cfg = parse(str(p))
    assert cfg.security.open and cfg.security.users == ["worker"]
    assert cfg.web.port == 8080


def test_hot_reload_excludes_connection_settings(tmp_path):
    p = tmp_path / "c.json"
    p.write_text(json.dumps({"lock_ttl": 100, "web": {"port": 1111}}))
    cfg = parse(str(p))
    reloaded = []
    w = ConfigWatcher(str(p), cfg, lambda c: reloaded.append(c),
                      poll_s=0.05, debounce_s=0.1)
    w.start()
    time.sleep(0.2)
    p.write_text(json.dumps({"lock_ttl": 200, "web": {"port": 2222}}))
    deadline = time.time() + 5
    while not reloaded and time.time() < deadline:
        time.sleep(0.05)
    w.stop()
    assert reloaded
    assert cfg.lock_ttl == 200         # reloaded
    assert cfg.web.port == 1111        # excluded from reload


# ------------------------------------------------------------------ events

def test_event_bus_on_emit_off_dedupe():
    events.clear()
    hits = []
    fn = lambda: hits.append(1)
    events.on("x", fn)
    events.on("x", fn)                  # dedupe
    events.emit("x")
    assert hits == [1]
    events.off("x", fn)
    events.emit("x")
    assert hits == [1]


def test_event_bus_arg_passing():
    events.clear()
    got = []
    events.on("cfg", lambda c: got.append(c))
    events.emit("cfg", {"a": 1})
    assert got == [{"a": 1}]


# ----------------------------------------------------------------- noticer

class CollectSender:
    def __init__(self):
        self.notices = []

    def send(self, n):
        self.notices.append(n)


def test_noticer_delivers_and_consumes():
    store = MemStore()
    sink = JobLogStore()
    sender = CollectSender()
    host = NoticerHost(store, sink, sender)
    store.put(KS.noticer_key("n1"),
              json.dumps({"subject": "s", "body": "b", "to": ["a@b.c"]}))
    assert host.poll() == 1
    assert sender.notices[0].subject == "s"
    assert store.get(KS.noticer_key("n1")) is None  # consumed


def test_noticer_node_fault_detection():
    store = MemStore()
    sink = JobLogStore()
    sender = CollectSender()
    host = NoticerHost(store, sink, sender)
    sink.upsert_node("n1", '{"id":"n1"}', alived=True)   # mirror says alive
    store.put(KS.node_key("n1"), "123")
    host.poll()
    store.delete(KS.node_key("n1"))                      # crash
    assert host.poll() == 1
    assert "down" in sender.notices[0].subject
    # clean shutdown: mirror says not alive -> no notice
    sink.set_node_alived("n1", False)
    store.put(KS.node_key("n1"), "123")
    host.poll()
    store.delete(KS.node_key("n1"))
    assert host.poll() == 0


def test_noticer_sender_failure_does_not_crash():
    store = MemStore()
    sink = JobLogStore()

    class Boom:
        def send(self, n):
            raise RuntimeError("smtp down")

    host = NoticerHost(store, sink, Boom())
    store.put(KS.noticer_key("n1"), json.dumps({"subject": "s", "body": "b"}))
    assert host.poll() == 0


def test_event_bus_bound_method_arity():
    """emit must not pass the arg to zero-arg bound methods (co_argcount
    counts self; server.stop() as an EXIT handler used to blow up)."""
    from cronsun_tpu import events

    class Srv:
        def __init__(self):
            self.stopped = 0
            self.seen = []

        def stop(self):
            self.stopped += 1

        def reload(self, cfg):
            self.seen.append(cfg)

    s = Srv()
    events.clear()
    events.on("x", s.stop, s.reload)
    events.emit("x", "cfg1")
    assert s.stopped == 1
    assert s.seen == ["cfg1"]
    events.clear()


def test_events_shutdown_releases_wait():
    """events.shutdown() must release a blocked events.wait() — the fatal
    path a component takes when the process must wind down without an
    operator signal."""
    import threading
    import time
    from cronsun_tpu import events

    events.clear()
    done = []
    t = threading.Thread(target=lambda: (events.wait(), done.append(1)),
                         daemon=True)
    t.start()
    time.sleep(0.2)
    assert not done
    events.shutdown()
    t.join(timeout=3)
    assert done, "wait() did not release on shutdown()"
    events.clear()


def test_events_shutdown_before_wait_is_sticky():
    """A shutdown() fired before main reaches wait() (supervised child
    dying between READY and wait, bin/store.py) must release wait()
    immediately, not be swallowed."""
    import threading
    from cronsun_tpu import events

    events.clear()
    events.shutdown()                    # fires BEFORE wait() starts
    done = []
    t = threading.Thread(target=lambda: (events.wait(), done.append(1)),
                         daemon=True)
    t.start()
    t.join(timeout=3)
    assert done, "pre-wait shutdown() was lost"
    events.clear()


class FlakySender:
    """Fails the first ``fail_n`` sends, then delivers."""

    def __init__(self, fail_n=1):
        self.fail_n = fail_n
        self.attempts = 0
        self.notices = []

    def send(self, n):
        self.attempts += 1
        if self.attempts <= self.fail_n:
            raise RuntimeError("smtp down")
        self.notices.append(n)


def test_noticer_failed_send_retries_and_key_survives():
    """A failed delivery must NOT consume the noticer key; the alert is
    retried with backoff and the key is deleted only on success."""
    store = MemStore()
    sink = JobLogStore()
    sender = FlakySender(fail_n=1)
    host = NoticerHost(store, sink, sender)
    host.RETRY_CAP = 0.01                # fast test
    store.put(KS.noticer_key("n1"), json.dumps({"subject": "s", "body": "b"}))
    assert host.poll() == 0              # first attempt fails
    assert store.get(KS.noticer_key("n1")) is not None, \
        "key consumed despite failed delivery"
    # wait out the 0.5s first-attempt backoff, then retry succeeds
    deadline = time.time() + 5
    delivered = 0
    while not delivered and time.time() < deadline:
        time.sleep(0.05)
        delivered = host.poll()
    assert delivered == 1
    assert sender.notices[0].subject == "s"
    assert store.get(KS.noticer_key("n1")) is None   # consumed on success


def test_noticer_failed_send_survives_restart():
    """Because the key survives a failed send, a fresh NoticerHost
    (process restart) re-lists and delivers it."""
    store = MemStore()
    sink = JobLogStore()

    class Boom:
        def send(self, n):
            raise RuntimeError("smtp down")

    host = NoticerHost(store, sink, Boom())
    store.put(KS.noticer_key("n1"), json.dumps({"subject": "s", "body": "b"}))
    assert host.poll() == 0
    # "restart": new host, working sender
    sender = CollectSender()
    host2 = NoticerHost(store, sink, sender)
    assert host2.resync() == 1
    assert sender.notices[0].subject == "s"
    assert store.get(KS.noticer_key("n1")) is None


def test_noticer_parked_notice_replaced_by_newer_overwrite():
    """Agents overwrite ONE per-node noticer key; while a delivery is
    parked awaiting retry, a newer notice at the same key must replace
    the parked one — delivering the stale value and deleting the key
    would lose the newer notice permanently."""
    store = MemStore()
    sink = JobLogStore()
    sender = FlakySender(fail_n=1)
    host = NoticerHost(store, sink, sender)
    key = KS.noticer_key("n1")
    store.put(key, json.dumps({"subject": "A", "body": "old"}))
    assert host.poll() == 0                  # A parks
    store.put(key, json.dumps({"subject": "B", "body": "new"}))
    host.poll()                              # B replaces parked A
    deadline = time.time() + 5
    while not sender.notices and time.time() < deadline:
        time.sleep(0.05)
        host.poll()
    assert [n.subject for n in sender.notices] == ["B"], \
        "stale parked notice delivered instead of the newer overwrite"
    assert store.get(key) is None


def test_noticer_node_reregister_during_retry_keeps_mirror_alive():
    """If the node re-registers while its crash alert awaits retry, the
    eventual delivery must NOT flip the mirror dead — that would swallow
    the alert for the node's next real crash."""
    store = MemStore()
    sink = JobLogStore()
    sender = FlakySender(fail_n=1)
    host = NoticerHost(store, sink, sender)
    sink.upsert_node("nx", '{"id": "nx"}', alived=True)
    store.put(KS.node_key("nx"), "host:1")
    host.poll()
    store.delete(KS.node_key("nx"))                  # crash
    assert host.poll() == 0                          # alert parks
    store.put(KS.node_key("nx"), "host:2")           # node comes back
    sink.upsert_node("nx", '{"id": "nx"}', alived=True)
    deadline = time.time() + 5
    while not sender.notices and time.time() < deadline:
        time.sleep(0.05)
        host.poll()
    assert len(sender.notices) == 1                  # alert delivered
    assert sink.get_node("nx")["alived"], \
        "mirror flipped dead although the node re-registered"


def test_noticer_node_down_mirror_marked_only_after_delivery():
    """The alived mirror flips to dead only once the crash alert is
    actually delivered, so an undelivered alert is recoverable by
    resync; the pending dedupe stops double-queueing meanwhile."""
    store = MemStore()
    sink = JobLogStore()
    sender = FlakySender(fail_n=1)
    host = NoticerHost(store, sink, sender)
    sink.upsert_node("nx", '{"id": "nx"}', alived=True)
    store.put(KS.node_key("nx"), "host:1")
    host.poll()
    store.delete(KS.node_key("nx"))                  # crash
    assert host.poll() == 0                          # delivery failed
    assert sink.get_node("nx")["alived"], \
        "mirror marked dead before the alert was delivered"
    host.resync()                                    # must not double-queue
    assert len(host._pending) == 1
    deadline = time.time() + 5
    while not sender.notices and time.time() < deadline:
        time.sleep(0.05)
        host.poll()
    assert len(sender.notices) == 1
    assert not sink.get_node("nx")["alived"]         # marked after delivery


def test_node_crash_alert_not_repeated_on_resync():
    """A crash alert marks the mirror dead, so a later resync (watch
    loss) must not re-mail the same crash; a node that re-registers and
    crashes again alerts again."""
    from cronsun_tpu.core import Keyspace
    from cronsun_tpu.logsink import JobLogStore
    from cronsun_tpu.noticer import NoticerHost
    from cronsun_tpu.store import MemStore
    ks = Keyspace()
    store, sink = MemStore(), JobLogStore()
    sink.upsert_node("nx", '{"id": "nx"}', alived=True)
    host = NoticerHost(store, sink, CollectSender())
    # crash: node key vanished while mirror says alive
    store.put(ks.node_key("nx"), "host:1")
    store.delete(ks.node_key("nx"))
    host.poll()
    downs = [n for n in host.sent if "down" in n.subject]
    assert len(downs) == 1
    # watch-loss resyncs must not re-alert the handled crash
    host.resync()
    host.resync()
    downs = [n for n in host.sent if "down" in n.subject]
    assert len(downs) == 1, "crash re-alerted on resync"
    # node comes back, crashes again -> one new alert
    sink.upsert_node("nx", '{"id": "nx"}', alived=True)
    host.resync()
    downs = [n for n in host.sent if "down" in n.subject]
    assert len(downs) == 2
    store.close()
