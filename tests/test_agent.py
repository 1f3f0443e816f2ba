"""NodeAgent unit tests: dispatch timing and lease-lapse recovery.

The scheduler publishes the whole planned window [t+1, t+W] ahead of
wall-clock; the agent must hold each order until its cron instant (the
reference only ever fires late, never early — cron.go:212-215).
"""

import json
import time

import pytest

from cronsun_tpu.core import Job, JobRule, Keyspace, KIND_COMMON
from cronsun_tpu.logsink import JobLogStore
from cronsun_tpu.node.agent import NodeAgent
from cronsun_tpu.node.executor import ExecResult
from cronsun_tpu.store import MemStore

KS = Keyspace()


def make_job(name="j", command="echo hi"):
    job = Job(name=name, command=command, kind=KIND_COMMON,
              rules=[JobRule(timer="* * * * * *", nids=["n0"])])
    job.check()
    return job


def test_dispatch_waits_for_scheduled_second():
    store, sink = MemStore(), JobLogStore()
    t = [1_753_000_000.0]
    agent = NodeAgent(store, sink, node_id="n0", clock=lambda: t[0])
    agent.register()
    job = make_job()
    store.put(KS.job_key(job.group, job.id), job.to_json())
    epoch = int(t[0]) + 3   # order for 3 (virtual) seconds in the future
    store.put(KS.dispatch_key("n0", epoch, job.group, job.id),
              json.dumps({"rule": job.rules[0].id, "kind": job.kind}))
    agent.poll()
    time.sleep(0.3)         # real time passes; the virtual second hasn't
    _, total = sink.query_logs(job_ids=[job.id])
    assert total == 0, "job ran before its scheduled second"
    t[0] = epoch + 0.5      # the second arrives
    agent.join_running()
    _, total = sink.query_logs(job_ids=[job.id])
    assert total == 1
    store.close()


def test_past_dispatch_runs_immediately():
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    job = make_job()
    store.put(KS.job_key(job.group, job.id), job.to_json())
    epoch = int(time.time()) - 5    # late order: run now, not never
    store.put(KS.dispatch_key("n0", epoch, job.group, job.id),
              json.dumps({"rule": job.rules[0].id, "kind": job.kind}))
    agent.poll()
    agent.join_running()
    _, total = sink.query_logs(job_ids=[job.id])
    assert total == 1
    store.close()


def test_stop_abandons_pending_future_orders():
    store, sink = MemStore(), JobLogStore()
    t = [1_753_000_000.0]
    agent = NodeAgent(store, sink, node_id="n0", clock=lambda: t[0])
    agent.register()
    job = make_job()
    store.put(KS.job_key(job.group, job.id), job.to_json())
    store.put(KS.dispatch_key("n0", int(t[0]) + 3600, job.group, job.id),
              json.dumps({"rule": job.rules[0].id, "kind": job.kind}))
    agent.poll()
    agent.stop()            # must not hang on the hour-away order
    _, total = sink.query_logs(job_ids=[job.id])
    assert total == 0
    store.close()


def test_broadcast_not_mine_is_remembered_until_the_job_or_a_group_changes():
    """Every agent sees every Common fire of the fleet.  A job judged
    "not mine" is not fetched again on its next fire — and IS judged
    again once its document changes, a group changes, or the watches
    resync, so an edit that makes this node eligible takes effect at
    the next fire."""
    from cronsun_tpu.core import Group
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    job = Job(name="elsewhere", command="echo hi", kind=KIND_COMMON,
              rules=[JobRule(timer="* * * * * *", nids=["n9"],
                             gids=["g"])])
    job.check()
    key, pair = KS.job_key(job.group, job.id), (job.group, job.id)
    gj = f"{job.group}/{job.id}"
    store.put(key, job.to_json())
    fetches = []
    real_get, real_many = store.get, store.get_many
    store.get = lambda k: (fetches.append(k), real_get(k))[1]
    store.get_many = lambda ks_: (fetches.extend(ks_), real_many(ks_))[1]
    epoch = [int(time.time()) - 100]

    def fire():
        epoch[0] += 1
        store.put(KS.dispatch_all_key(epoch[0], job.group, job.id), "")
        n = agent.poll()
        agent.join_running()
        return n

    assert fire() == 0 and gj in agent._not_here
    assert pair not in agent._job_cache
    del fetches[:]
    assert fire() == 0 and key not in fetches      # no second fetch
    # the job's document changes: judged afresh, and now it is mine
    job.rules[0].nids = ["n0"]
    store.put(key, job.to_json())
    assert fire() == 1 and gj not in agent._not_here
    # back to "elsewhere" ... until a group that the rule names gains
    # this node
    job.rules[0].nids = ["n9"]
    store.put(key, job.to_json())
    assert fire() == 0 and gj in agent._not_here
    store.put(KS.group_key("g"),
              Group(id="g", name="g", node_ids=["n0"]).to_json())
    assert fire() == 1
    # bounded: at the cap the set starts over, as the job cache does
    agent._not_here_cap = 1
    agent._not_here.add("g/other")
    job.rules[0].gids = []
    store.put(key, job.to_json())
    assert fire() == 0 and agent._not_here == {gj}
    # a watch resync forgets every verdict (and judges the re-listed
    # broadcasts afresh)
    agent._not_here_cap = 1 << 20
    agent._not_here.add("g/stale")
    agent.resync_watches()
    assert agent._not_here == {gj}
    _, total = sink.query_logs(job_ids=[job.id])
    assert total == 2
    store.close()


def test_proc_keys_survive_lease_reregister():
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    old_proc_lease = agent._proc_lease
    job = make_job(name="slow", command="sleep 1")
    store.put(KS.job_key(job.group, job.id), job.to_json())
    agent._spawn(job, int(time.time()) - 1, fenced=False)
    deadline = time.time() + 3
    while time.time() < deadline and not store.get_prefix(KS.proc):
        time.sleep(0.02)
    assert store.get_prefix(KS.proc), "proc key never appeared"
    # simulate a full connectivity lapse: both leases expire, the leased
    # proc key dies with them
    store.revoke(agent._lease)
    store.revoke(old_proc_lease)
    assert not store.get_prefix(KS.proc)
    agent.keepalive_once()          # re-registers + repairs the proc lease
    assert store.get_prefix(KS.proc), \
        "running execution vanished from the proc registry after re-register"
    agent.join_running()
    assert not store.get_prefix(KS.proc)
    store.close()


def test_proc_lease_lapse_repaired_by_keepalive():
    """If the proc lease expires while the node lease stays healthy,
    keepalive_once must grant a fresh proc lease and re-attach running
    proc keys."""
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    job = make_job(name="slow2", command="sleep 1")
    store.put(KS.job_key(job.group, job.id), job.to_json())
    agent._spawn(job, int(time.time()) - 1, fenced=False)
    deadline = time.time() + 3
    while time.time() < deadline and not store.get_prefix(KS.proc):
        time.sleep(0.02)
    assert store.get_prefix(KS.proc)
    store.revoke(agent._proc_lease)     # proc lease dies, node lease lives
    assert not store.get_prefix(KS.proc)
    agent.keepalive_once()
    assert store.get_prefix(KS.proc), "proc key not re-attached after repair"
    agent.join_running()
    store.close()


def test_node_lease_survives_a_stalled_main_connection():
    """The node lease is refreshed on its own connection and thread: a
    main connection that is seconds behind (the agent's own bulk RPCs
    and queued watch pushes, at 1M jobs x 10k nodes) delays the round's
    housekeeping, never the refresh the fleet judges liveness by."""
    from cronsun_tpu.store.remote import RemoteStore, StoreServer
    srv = StoreServer().start()
    main = RemoteStore(srv.host, srv.port)
    agent = NodeAgent(main, JobLogStore(), node_id="n0", ttl=1.0)
    stalled = []

    def stall(_lease):               # the proc-lease leg of a round
        stalled.append(time.monotonic())
        time.sleep(4.5)              # > node lease (ttl + 2 = 3 s)
        return True
    try:
        agent.start()
        assert agent._lease_conn() is not main
        registered = main.get(KS.node_key("n0")).mod_rev
        main.keepalive = stall
        deadline = time.monotonic() + 6.0
        while time.monotonic() < deadline:
            kv = srv.store.get(KS.node_key("n0"))
            assert kv is not None and kv.mod_rev == registered, \
                "node lease lapsed behind the stalled main connection"
            time.sleep(0.2)
        assert stalled, "the stall was never reached"
    finally:
        del main.keepalive
        agent.stop()
        main.close()
        srv.stop()


def test_duplicate_node_guard():
    """A second agent claiming the same node identity while the first's
    PID is alive must be refused (reference node.go:51-79); a stale
    same-host registration from a dead PID is taken over; a foreign
    host's registration is refused while its lease lives (we cannot
    probe a remote PID)."""
    import os
    import socket
    import pytest
    from cronsun_tpu.core.errors import DuplicateNode
    me = socket.gethostname()
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    # live same-host foreign pid owns the identity -> refuse
    store.put(KS.node_key("n0"), f"{me}:{os.getppid()}")
    with pytest.raises(DuplicateNode):
        agent.register()
    # another machine's registration -> refuse regardless of local pids
    store.put(KS.node_key("n0"), f"other-host:{os.getppid()}")
    with pytest.raises(DuplicateNode):
        agent.register()
    # stale same-host pid (dead process) -> take over
    store.put(KS.node_key("n0"), f"{me}:999999999")
    agent.register()
    assert store.get(KS.node_key("n0")).value == f"{me}:{os.getpid()}"
    # own registration (keepalive re-register path) -> fine
    agent.register()
    store.close()


def test_duplicate_on_reregister_is_fatal():
    """If the identity is lost to a live replacement while running, the
    keepalive loop must stop the agent and fire on_fatal — a ghost that
    keeps polling would execute orders meant for the replacement."""
    import os
    import socket
    fatal = []
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0", ttl=0.3,
                      on_fatal=fatal.append)
    agent.start()
    # replacement takes the identity; kill our lease so keepalive lapses
    store.revoke(agent._lease)
    store.put(KS.node_key("n0"), f"{socket.gethostname()}:{os.getppid()}")
    deadline = time.time() + 5
    while time.time() < deadline and not fatal:
        time.sleep(0.05)
    assert fatal, "agent did not report fatal identity loss"
    assert agent._stop.is_set()
    store.close()


def test_order_consumed_on_fence_lost_skip():
    """An execution skipped because another node won the (job, second)
    fence must still consume its dispatch order key — a leaked order
    wrongly reserves scheduler capacity for the whole dispatch lease."""
    store = MemStore()
    sink = JobLogStore()
    agent = NodeAgent(store, sink, node_id="na", clock=lambda: 2_000_000.0)
    job = Job(id="fj", name="f", group="g", command="echo x", kind=2,
              rules=[JobRule(id="r", timer="* * * * * *", nids=["na"])])
    store.put(KS.job_key("g", "fj"), job.to_json())
    epoch = 1_999_999
    # another node already holds the fence
    store.put(KS.lock_key("fj", epoch), "other-node")
    order_key = KS.dispatch_key("na", epoch, "g", "fj")
    store.put(order_key, '{"rule":"r","kind":2}')
    job2 = agent._get_job("g", "fj")
    agent._execute(job2, epoch, fenced=True, order_key=order_key)
    assert store.get(order_key) is None, \
        "skipped execution leaked its dispatch order"
    _, total = sink.query_logs()
    assert total == 0                      # and really did not run
    store.close()


def test_exec_pool_workers_are_daemons():
    """Execution workers must be daemon threads: process exit must never
    block behind a long-running job command."""
    import threading as _t
    store = MemStore()
    agent = NodeAgent(store, JobLogStore(), node_id="nd")
    agent._ensure_pool()
    workers = [t for t in _t.enumerate()
               if t.name.startswith("exec-nd")]
    assert workers, "pool spawned no workers"
    assert all(t.daemon for t in workers)
    store.close()


def test_run_now_not_starved_by_saturated_pool():
    """A run-now trigger must start immediately even when every pool
    worker is occupied by long-running executions."""
    import threading as _t
    store = MemStore()
    sink = JobLogStore()

    release = _t.Event()
    calls = []

    class Blocking:
        def run_job(self, **kw):
            calls.append(1)
            if len(calls) <= 2:           # only the pool-saturating runs
                release.wait(10)
            now = time.time()
            return ExecResult(success=True, output="x",
                              begin_ts=now, end_ts=now)

    agent = NodeAgent(store, sink, node_id="nb", executor=Blocking())
    agent.max_inflight = 2
    job = Job(id="bk", name="b", group="g", command="echo x", kind=0,
              rules=[JobRule(id="r", timer="* * * * * *", nids=["nb"])])
    store.put(KS.job_key("g", "bk"), job.to_json())
    j = agent._get_job("g", "bk")
    now = int(time.time())
    # saturate both workers
    agent._spawn(j, now, fenced=False)
    agent._spawn(j, now, fenced=False)
    time.sleep(0.3)
    # run-now bypasses the pool
    agent._spawn(j, now, fenced=False, use_gate=False, immediate=True)
    deadline = time.time() + 5
    while time.time() < deadline:
        _, total = sink.query_logs()
        if total >= 1:
            break
        time.sleep(0.05)
    _, total = sink.query_logs()
    assert total >= 1, "run-now starved behind pool backlog"
    release.set()
    agent.join_running()
    store.close()


def test_future_orders_do_not_occupy_workers():
    """Orders for future epochs (the scheduler publishes whole windows
    ahead) stage on timers; a due order queued after them must not wait
    behind sleepers."""
    store = MemStore()
    sink = JobLogStore()
    agent = NodeAgent(store, sink, node_id="nf")
    agent.max_inflight = 1                 # a single worker
    job = Job(id="fut", name="f", group="g", command="echo x", kind=0,
              rules=[JobRule(id="r", timer="* * * * * *", nids=["nf"])])
    store.put(KS.job_key("g", "fut"), job.to_json())
    j = agent._get_job("g", "fut")
    now = int(time.time())
    agent._spawn(j, now + 4, fenced=False)   # future: staged, not queued
    agent._spawn(j, now, fenced=False)       # due now
    deadline = time.time() + 3
    while time.time() < deadline:
        _, total = sink.query_logs()
        if total >= 1:
            break
        time.sleep(0.05)
    _, total = sink.query_logs()
    assert total >= 1, "due order starved behind a staged future order"
    store.close()


def test_stop_drops_staged_future_orders():
    """stop() must cancel staged future-order timers promptly (no 10s
    join wait) and nothing may execute after stop — a stopped node's
    order must not resurrect the pool later."""
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="ns")
    job = make_job()
    store.put(KS.job_key(job.group, job.id), job.to_json())
    j = agent._get_job(job.group, job.id)
    agent._spawn(j, int(time.time()) + 2, fenced=False)
    assert agent._staged, "future order was not staged"
    t0 = time.time()
    agent.stop()
    assert time.time() - t0 < 5, "stop() blocked on staged work"
    assert not agent._staged and not agent.running
    time.sleep(2.5)                    # past the order's epoch
    _, total = sink.query_logs()
    assert total == 0, "staged order executed after stop()"
    assert agent._pool is None, "pool resurrected after stop()"
    store.close()


def test_staged_order_honors_virtual_clock():
    """Staging re-checks the INJECTED clock with bounded real naps (the
    _wait_until contract): advancing a virtual clock releases a staged
    order within ~a nap, not after its real-time delay."""
    store, sink = MemStore(), JobLogStore()
    t = [1_753_000_000.0]
    agent = NodeAgent(store, sink, node_id="nv", clock=lambda: t[0])
    agent.register()
    job = make_job(name="vj")
    job.rules[0].nids = ["nv"]
    store.put(KS.job_key(job.group, job.id), job.to_json())
    epoch = int(t[0]) + 3600           # an hour of VIRTUAL time away
    store.put(KS.dispatch_key("nv", epoch, job.group, job.id),
              json.dumps({"rule": job.rules[0].id, "kind": job.kind}))
    agent.poll()
    time.sleep(0.7)
    _, total = sink.query_logs()
    assert total == 0                  # virtual hour hasn't passed
    t[0] = epoch + 0.5                 # virtual clock jumps
    deadline = time.time() + 5
    while time.time() < deadline:
        _, total = sink.query_logs()
        if total:
            break
        time.sleep(0.1)
    _, total = sink.query_logs()
    assert total == 1, "staged order ignored the virtual clock"
    agent.stop()
    store.close()


def test_cron_context_env():
    """Executed commands see the cron-context environment — most
    importantly CRONSUN_SCHEDULED_TS, the second the run was planned
    FOR (begin_ts records when it actually ran; under load the two
    differ) — merged over the agent's own environment, not replacing
    it (PATH must survive for `sh` to resolve)."""
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    job = make_job(command="sh -c 'echo $CRONSUN_SCHEDULED_TS "
                           "$CRONSUN_JOB_ID $CRONSUN_JOB_GROUP "
                           "$CRONSUN_NODE'")
    store.put(KS.job_key(job.group, job.id), job.to_json())
    epoch = int(time.time()) - 1
    store.put(KS.dispatch_key("n0", epoch, job.group, job.id),
              json.dumps({"rule": job.rules[0].id, "kind": job.kind}))
    agent.poll()
    agent.join_running()
    recs, total = sink.query_logs(job_ids=[job.id])
    assert total == 1
    assert recs[0].output.split() == \
        [str(epoch), job.id, job.group, "n0"]
    store.close()


def test_claim_indeterminate_reply_still_runs_once():
    """A claim that APPLIES server-side but whose reply is lost (reply
    dropped on reconnect / batcher timeout) must not skip the execution:
    the fence holds this attempt's nonce, so the fallback reads it back
    as a win and proceeds — and a second agent still loses."""
    class LostReplyStore(MemStore):
        def __init__(self):
            super().__init__()
            self.drop_replies = 0

        def claim_many(self, items, fence_lease=0, proc_lease=0):
            out = super().claim_many(items, fence_lease, proc_lease)
            if self.drop_replies > 0:
                self.drop_replies -= 1
                raise RuntimeError("connection closed")   # applied, reply lost
            return out

    store, sink = LostReplyStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    job = Job(id="ix", name="ix", group="g", command="echo x", kind=2,
              rules=[JobRule(id="r", timer="* * * * * *", nids=["n0"])])
    store.put(KS.job_key(job.group, job.id), job.to_json())
    epoch = int(time.time()) - 2
    order = KS.dispatch_key("n0", epoch, job.group, job.id)
    store.put(order, json.dumps({"rule": "r", "kind": 2}))
    store.drop_replies = 1
    agent.poll()
    agent.join_running()
    _, total = sink.query_logs(job_ids=[job.id])
    assert total == 1, "indeterminate claim must not skip the execution"
    assert store.get(order) is None, "order consumed"
    # the fence key survives with this agent's nonce value
    fences = store.get_prefix(KS.lock)
    assert any(kv.value.startswith("n0@") for kv in fences)
    # a second agent's claim for the same (job, second) still loses
    agent2 = NodeAgent(store, sink, node_id="n1")
    agent2.register()
    job2 = Job(id="ix", name="ix", group="g", command="echo x", kind=2,
               rules=[JobRule(id="r", timer="* * * * * *", nids=["n1"])])
    order2 = KS.dispatch_key("n1", epoch, job.group, job.id)
    store.put(order2, json.dumps({"rule": "r", "kind": 2}))
    agent2.poll()
    agent2.join_running()
    _, total = sink.query_logs(job_ids=[job.id])
    assert total == 1, "exactly-once must hold across the lost reply"
    agent.stop()
    agent2.stop()
    store.close()


def test_claim_many_malformed_item_is_per_item_false():
    """Backend parity (stored.cc): a short item yields False without
    aborting or half-applying the batch."""
    store = MemStore()
    lease = store.grant(30)
    out = store.claim_many(
        [("/lk/a", "v", "", "", ""),
         ("/lk/bad",),                      # malformed: too short
         ("/lk/c", "v", "", "", "")], fence_lease=lease)
    assert out == [True, False, True]
    assert store.get("/lk/a") is not None
    assert store.get("/lk/bad") is None
    assert store.get("/lk/c") is not None
    store.close()


def test_record_flush_retries_without_loss_or_duplicates():
    """A sink hiccup must not drop a whole flush batch (ADVICE r4): the
    failed batch parks in the retry slot with its idempotency token
    pinned and lands once the sink heals — no loss, no duplicates, and
    records that arrive DURING the outage ride a separate batch."""
    store, real = MemStore(), JobLogStore()

    class FlakySink:
        def __init__(self):
            self.fail = 0
            self.idems = []

        def create_job_logs(self, recs, idem=""):
            if self.fail > 0:
                self.fail -= 1
                raise OSError("sink down")
            self.idems.append(idem)
            return real.create_job_logs(recs, idem=idem)

        def query_logs(self, **kw):
            return real.query_logs(**kw)

        def set_node_alived(self, *a, **kw):
            pass

    sink = FlakySink()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.rec_flush_interval = 3600     # flush only when the test says
    job = make_job()

    def rec(i):
        agent._record(job, ExecResult(
            success=True, output=f"r{i}", error="",
            begin_ts=time.time(), end_ts=time.time(), skipped=False))

    rec(0)
    rec(1)
    sink.fail = 2
    agent._flush_records()              # fails -> parks in retry slot
    rec(2)                              # arrives during the outage
    agent._rec_retry_at = 0.0           # collapse the backoff window
    agent._flush_records()              # retry fails again; fresh waits
    agent._rec_retry_at = 0.0
    agent._flush_records()              # sink healed: retry batch + fresh
    agent._flush_records()
    _, total = real.query_logs(job_ids=[job.id])
    assert total == 3, "records lost or duplicated across the outage"
    # the parked batch kept ONE token across its attempts; the fresh
    # batch rode its own
    assert len(sink.idems) == 2 and sink.idems[0] != sink.idems[1]
    agent.stop()
    store.close()


def test_per_record_tokens_stable_across_flush_retry():
    """The degraded per-record path (a sink without create_job_logs):
    an attempt that COMMITS but loses its reply must dedup on the
    agent-level retry — the retry re-sends the SAME per-record
    idempotency token (the logsink/serve.py token contract), where a
    fresh token per call would double-insert the record."""
    store = MemStore()

    class IndetSink:
        """Minimal per-record sink with server-side idem dedup; the
        first N calls commit and then raise (reply lost)."""

        def __init__(self):
            self.rows = {}       # idem -> rec (the dedup table)
            self.fail = 0

        def create_job_log(self, rec, idem=""):
            assert idem, "agent must pass a per-record token"
            if idem not in self.rows:
                self.rows[idem] = rec
            if self.fail > 0:
                self.fail -= 1
                raise OSError("reply lost")

        def set_node_alived(self, *a, **kw):
            pass

    sink = IndetSink()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.rec_flush_interval = 3600
    job = make_job()
    for i in range(3):
        agent._record(job, ExecResult(
            success=True, output=f"r{i}", error="",
            begin_ts=time.time(), end_ts=time.time(), skipped=False))
    sink.fail = 2                       # first two records: commit, then
    agent._flush_records()              # "fail" -> head committed twice
    agent._rec_retry_at = 0.0
    agent._flush_records()              # retry the unwritten-looking tail
    agent._rec_retry_at = 0.0
    agent._flush_records()
    assert agent._rec_retry is None and not agent._rec_buf
    assert len(sink.rows) == 3, (
        f"indeterminate per-record writes double-inserted: "
        f"{len(sink.rows)} rows for 3 executions")
    agent.stop()
    store.close()


def test_record_flush_final_drop_is_not_silent():
    """stop()'s final flush cannot retry: a still-down sink means the
    batch is dropped — and dropped loudly, not parked behind a 'retry'
    log line that will never happen."""
    store = MemStore()

    class DeadSink:
        def create_job_logs(self, recs, idem=""):
            raise OSError("sink down")

        def query_logs(self, **kw):
            return [], 0

        def set_node_alived(self, *a, **kw):
            pass

    agent = NodeAgent(store, DeadSink(), node_id="n0")
    agent.rec_flush_interval = 3600
    job = make_job()
    agent._record(job, ExecResult(
        success=True, output="x", error="",
        begin_ts=time.time(), end_ts=time.time(), skipped=False))
    agent._flush_records(final=True)
    assert agent._rec_retry is None and not agent._rec_buf
    agent.stop()
    store.close()


# ---- coalesced (node, second) order bundles -----------------------------

def _bundle(jobs, epoch):
    """Coalesced order value for [(group, id), ...] — the wire format the
    scheduler publishes (one key per (node, second))."""
    return json.dumps([f"{g}/{j}" for g, j in jobs])


def _seed_excl(store, n, prefix="bz", nid="n0"):
    jobs = []
    for i in range(n):
        job = Job(id=f"{prefix}{i}", name=f"{prefix}{i}", group="g",
                  command="echo b", kind=2,
                  rules=[JobRule(id="r", timer="* * * * * *", nids=[nid])])
        store.put(KS.job_key("g", job.id), job.to_json())
        jobs.append(("g", job.id))
    return jobs


def test_bundle_consumed_with_exactly_once_fences():
    """A coalesced bundle runs every member once; a DUPLICATE delivery
    of the same (node, second) bundle (hole-rewind overwrite, resync
    re-list) loses every fence and runs nothing — per-job exactly-once
    rests on the (job, second) fences exactly as before coalescing."""
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    jobs = _seed_excl(store, 3)
    epoch = int(time.time()) - 1
    key = KS.dispatch_bundle_key("n0", epoch)
    store.put(key, _bundle(jobs, epoch))
    agent.poll()
    agent.join_running()
    _, total = sink.query_logs()
    assert total == 3
    assert store.get(key) is None, "reservation key not consumed"
    # every member holds this agent's nonce fence
    fences = store.get_prefix(KS.lock)
    assert len(fences) == 3
    assert all(kv.value.startswith("n0@") for kv in fences)
    # duplicate delivery: re-claim loses on every fence, zero re-runs
    store.put(key, _bundle(jobs, epoch))
    agent.poll()
    agent.join_running()
    _, total = sink.query_logs()
    assert total == 3, "duplicate bundle re-ran a member"
    assert store.get(key) is None
    agent.stop()
    store.close()


def test_partial_bundle_releases_reservation_without_double_fire():
    """One member's fence is already held (another node ran it): the
    others run, the pre-fenced one does not, and the bundle key — the
    capacity reservation — is consumed exactly once in the same atomic
    op that writes the winners' fences (no leak, no double-fire)."""
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    jobs = _seed_excl(store, 3, prefix="pz")
    epoch = int(time.time()) - 1
    # (pz1, epoch) already ran elsewhere
    store.put(KS.lock_key("pz1", epoch), "other-node")
    key = KS.dispatch_bundle_key("n0", epoch)
    store.put(key, _bundle(jobs, epoch))
    agent.poll()
    agent.join_running()
    recs, total = sink.query_logs()
    assert total == 2
    assert {r.job_id for r in recs} == {"pz0", "pz2"}
    assert store.get(key) is None, "partial consumption leaked the key"
    assert store.get(KS.lock_key("pz1", epoch)).value == "other-node"
    agent.stop()
    store.close()


def test_bundle_tolerates_legacy_keys_side_by_side():
    """Rollout tolerance: a legacy per-(node, second, job) order and a
    coalesced bundle drain in the same poll, each exactly once."""
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    jobs = _seed_excl(store, 2, prefix="mx")
    legacy = Job(id="lg", name="lg", group="g", command="echo l", kind=2,
                 rules=[JobRule(id="r", timer="* * * * * *", nids=["n0"])])
    store.put(KS.job_key("g", "lg"), legacy.to_json())
    epoch = int(time.time()) - 1
    store.put(KS.dispatch_bundle_key("n0", epoch), _bundle(jobs, epoch))
    store.put(KS.dispatch_key("n0", epoch, "g", "lg"),
              '{"rule":"r","kind":2}')
    agent.poll()
    agent.join_running()
    recs, total = sink.query_logs()
    assert total == 3
    assert {r.job_id for r in recs} == {"mx0", "mx1", "lg"}
    assert not [kv for kv in store.get_prefix(KS.dispatch)], \
        "orders left unconsumed"
    agent.stop()
    store.close()


@pytest.mark.parametrize("order", ["bundle", "bundle of it alone",
                                   "single order"])
@pytest.mark.parametrize("why", ["previous run live",
                                 "lock lease expired before its put"])
def test_bundle_alone_skip_does_not_consume_fence(why, order):
    """A KindAlone fire whose previous run still holds the lifetime
    lock is skipped here, on the node, at its second — the one place
    the lock is judged — WITHOUT consuming its (job, second) fence (the
    lock-first ordering survives coalescing), and counted
    (``alone_skipped_total``); the rest of its bundle runs, and the
    order key — the scheduler's capacity reservation — is released
    whichever way the order came: in a bundle with others (the claim
    takes the key), in a bundle with nothing else claimable, or as a
    late single order (both acked).  Likewise a fire whose fresh lock
    lease (5 s for a fast job) ran out before its put landed, behind a
    store round trip longer than that: the store refuses the put.  Once
    the lock is let go, the job's next order runs, once."""
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    jobs = _seed_excl(store, 1, prefix="az") if order == "bundle" else []
    alone = Job(id="alz", name="alz", group="g", command="echo a", kind=1,
                rules=[JobRule(id="r", timer="* * * * * *", nids=["n0"])])
    store.put(KS.job_key("g", "alz"), alone.to_json())
    real = store.put_if_absent
    if why == "previous run live":
        store.put(KS.alone_lock_key("alz"), "other")
    else:
        def late(key, value, lease=0):
            if key == KS.alone_lock_key("alz"):
                store.revoke(lease)             # its ttl ran out in flight
            return real(key, value, lease=lease)
        store.put_if_absent = late

    def deliver(epoch):
        if order == "single order":
            key = KS.dispatch_key("n0", epoch, "g", "alz")
            store.put(key, '{"rule":"r","kind":1}')
        else:
            key = KS.dispatch_bundle_key("n0", epoch)
            store.put(key, _bundle(jobs + [("g", "alz")], epoch))
        agent.poll()
        agent.join_running()
        return key

    epoch = int(time.time()) - 2
    key = deliver(epoch)
    recs, total = sink.query_logs()
    assert [r.job_id for r in recs] == [j for _g, j in jobs]
    assert store.get(KS.lock_key("alz", epoch)) is None, \
        "Alone skip consumed the fence"
    assert store.get(key) is None, "reservation not released"
    assert agent.metrics_snapshot()["alone_skipped_total"] == 1
    # the previous run ends: the next fire is ordered like any other
    store.delete(KS.alone_lock_key("alz"))
    store.put_if_absent = real
    key = deliver(epoch + 1)
    recs, total = sink.query_logs(job_ids=["alz"])
    assert total == 1, "the fire after the lock was let go ran once"
    assert store.get(KS.lock_key("alz", epoch + 1)) is not None
    assert store.get(key) is None
    assert store.get(KS.alone_lock_key("alz")) is None, "lock let go"
    assert agent.metrics_snapshot()["alone_skipped_total"] == 1
    agent.stop()
    store.close()


def test_bundle_falls_back_when_store_lacks_claim_bundle():
    """Degraded-store ladder: a store predating claim_bundle still
    consumes the bundle exactly once via per-item fences (N+1 RPCs,
    correct), and a second agent re-delivered the same bundle loses."""
    class OldStore(MemStore):
        def claim_bundle(self, *a, **kw):
            raise RuntimeError("unknown op 'claim_bundle'")

    store, sink = OldStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    jobs = _seed_excl(store, 3, prefix="fz")
    epoch = int(time.time()) - 1
    key = KS.dispatch_bundle_key("n0", epoch)
    store.put(key, _bundle(jobs, epoch))
    agent.poll()
    agent.join_running()
    _, total = sink.query_logs()
    assert total == 3
    assert store.get(key) is None
    store.put(key, _bundle(jobs, epoch))   # duplicate delivery
    agent.poll()
    agent.join_running()
    _, total = sink.query_logs()
    assert total == 3, "fallback path broke exactly-once"
    agent.stop()
    store.close()


def test_bundle_indeterminate_reply_still_runs_once():
    """claim_bundle APPLIES server-side but the reply is lost: the
    read-back finds this agent's nonces on every fence and proceeds —
    no member is skipped, none runs twice, the reservation is gone."""
    class LostBundleReplyStore(MemStore):
        drop_replies = 0

        def claim_bundle(self, *a, **kw):
            out = super().claim_bundle(*a, **kw)
            if LostBundleReplyStore.drop_replies > 0:
                LostBundleReplyStore.drop_replies -= 1
                raise RuntimeError("connection closed")
            return out

    store, sink = LostBundleReplyStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    jobs = _seed_excl(store, 2, prefix="iz")
    epoch = int(time.time()) - 1
    key = KS.dispatch_bundle_key("n0", epoch)
    store.put(key, _bundle(jobs, epoch))
    LostBundleReplyStore.drop_replies = 1
    agent.poll()
    agent.join_running()
    _, total = sink.query_logs()
    assert total == 2, "indeterminate bundle claim skipped executions"
    assert store.get(key) is None
    fences = store.get_prefix(KS.lock)
    assert len(fences) == 2
    assert all(kv.value.startswith("n0@") for kv in fences)
    agent.stop()
    store.close()


def test_bundle_waits_for_scheduled_second():
    """Bundles are staged like per-job orders: nothing in the bundle
    runs before its cron instant."""
    store, sink = MemStore(), JobLogStore()
    t = [1_753_000_000.0]
    agent = NodeAgent(store, sink, node_id="n0", clock=lambda: t[0])
    agent.register()
    jobs = _seed_excl(store, 2, prefix="wz")
    epoch = int(t[0]) + 3
    store.put(KS.dispatch_bundle_key("n0", epoch), _bundle(jobs, epoch))
    agent.poll()
    time.sleep(0.3)
    _, total = sink.query_logs()
    assert total == 0, "bundle ran before its scheduled second"
    t[0] = epoch + 0.5
    agent.join_running()
    _, total = sink.query_logs()
    assert total == 2
    agent.stop()
    store.close()


def test_forced_flush_does_not_burn_retry_budget():
    """ADVICE r5 medium: join_running()'s force=True flush attempts even
    inside the retry backoff window (the sink may have healed), but a
    FAILED forced attempt must not count toward rec_flush_max_fails — a
    caller polling join_running during a sink outage must not exhaust
    the ~minutes-long retry budget in seconds."""
    class DownSink(JobLogStore):
        def __init__(self):
            super().__init__()
            self.down = False

        def create_job_logs(self, recs, idem=None):
            if self.down:
                raise RuntimeError("sink down")
            return super().create_job_logs(recs, idem=idem)

    store, sink = MemStore(), DownSink()
    t = [1_753_000_000.0]
    agent = NodeAgent(store, sink, node_id="n0", clock=lambda: t[0])
    agent.register()
    from cronsun_tpu.logsink import LogRecord
    agent._rec_buf.append(LogRecord(
        job_id="j", job_group="g", name="j", node="n0", user="",
        command="true", output="", success=True, begin_ts=1.0, end_ts=2.0))
    sink.down = True
    agent._flush_records()              # parks the batch in the retry slot
    assert agent._rec_retry is not None
    fails_after_first = agent._rec_flush_fails
    # hammer the barrier INSIDE the backoff window: attempts happen but
    # the budget must not move
    for _ in range(20):
        agent.join_running(timeout=0.1)
    assert agent._rec_flush_fails == fails_after_first, \
        "forced barrier attempts burned the retry budget"
    assert agent._rec_retry is not None, "batch dropped early"
    # scheduled (non-forced) attempts past the backoff still count
    t[0] += 60.0
    agent._flush_records()
    assert agent._rec_flush_fails == fails_after_first + 1
    # and once the sink heals, a forced barrier flush delivers
    sink.down = False
    agent.join_running(timeout=1.0)
    assert agent._rec_retry is None
    _, total = sink.query_logs()
    assert total == 1
    store.close()


def test_bundle_failure_releases_alone_locks():
    """An error escaping mid-bundle (degraded-path fence raising on a
    transport failure) must not leak a live Alone keepalive: the
    lifetime lock the bundle acquired is released, so the job is not
    blocked fleet-wide until this agent restarts."""
    class BrokenStore(MemStore):
        broken = False

        def claim_bundle(self, *a, **kw):
            if BrokenStore.broken:
                raise RuntimeError("unknown op 'claim_bundle'")
            return super().claim_bundle(*a, **kw)

        def put_if_absent(self, key, value, lease=0):
            # fences fail; the alone LOCK acquire itself succeeds
            if BrokenStore.broken and key.startswith(KS.lock) \
                    and not key.startswith(KS.alone_lock):
                raise RuntimeError("transport down")
            return super().put_if_absent(key, value, lease=lease)

    store, sink = BrokenStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    alone = Job(id="lk", name="lk", group="g", command="echo a", kind=1,
                rules=[JobRule(id="r", timer="* * * * * *", nids=["n0"])])
    store.put(KS.job_key("g", "lk"), alone.to_json())
    epoch = int(time.time()) - 1
    key = KS.dispatch_bundle_key("n0", epoch)
    BrokenStore.broken = True
    store.put(key, json.dumps(["g/lk"]))
    agent.poll()
    agent.join_running()
    BrokenStore.broken = False
    assert store.get(KS.alone_lock_key("lk")) is None, \
        "bundle failure leaked the Alone lifetime lock"
    _, total = sink.query_logs()
    assert total == 0
    agent.stop()
    store.close()


def test_herd_second_records_every_execution_and_its_spawn_time():
    """64 executions due in ONE second on one agent, through the real
    Executor (a fork and an exec each): 40 exclusive fires of one
    (node, second) bundle and 24 Common broadcasts — a herd second's
    shape.  Every one is recorded once, each stamped with the second it
    was scheduled for, and the snapshot carries what the launches cost.
    No bound on the milliseconds: the CPU runners are shared."""
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="n0")
    agent.register()
    excl = _seed_excl(store, 40, prefix="hx")
    for _, jid in excl:     # the cell's command: prints its own second
        job = Job.from_json(store.get(KS.job_key("g", jid)).value)
        job.group, job.id = "g", jid
        job.command = "printenv CRONSUN_SCHEDULED_TS"
        store.put(KS.job_key("g", jid), job.to_json())
    common = []
    for i in range(24):
        job = Job(id=f"hc{i}", name=f"hc{i}", group="g",
                  command="printenv CRONSUN_SCHEDULED_TS", kind=KIND_COMMON,
                  rules=[JobRule(id="r", timer="* * * * * *", nids=["n0"])])
        store.put(KS.job_key("g", job.id), job.to_json())
        common.append(job.id)
    epoch = int(time.time()) - 1
    store.put(KS.dispatch_bundle_key("n0", epoch), _bundle(excl, epoch))
    for jid in common:
        store.put(KS.dispatch_all_key(epoch, "g", jid), "")
    deadline = time.time() + 60
    while time.time() < deadline:
        agent.poll()
        agent.join_running()
        if sink.query_logs()[1] >= 64:
            break
        time.sleep(0.05)
    recs, total = sink.query_logs(page_size=200)
    assert total == 64
    assert sorted(r.job_id for r in recs) == \
        sorted([j for _, j in excl] + common), "one record a job"
    assert all(r.success and r.output.strip() == str(epoch) for r in recs)
    snap = agent.metrics_snapshot()
    assert snap["execs_total"] == 64 and snap["execs_failed_total"] == 0
    assert snap["exec_spawn_p50_ms"] > 0
    assert snap["exec_spawn_p99_ms"] >= snap["exec_spawn_p50_ms"]
    assert snap["execs_demoted_total"] == 0
    assert len(agent._spawn_ring) == 64
    agent.stop()
    store.close()
