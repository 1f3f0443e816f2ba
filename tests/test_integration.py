"""End-to-end: store + scheduler + agents in one process.

The multi-node test harness the reference never had (SURVEY.md §4): real
MemStore watches, a real planner on the CPU backend, real subprocess
executions — only wall-clock is compressed by stepping the scheduler with
explicit epochs.
"""

import json
import time

import pytest

from cronsun_tpu.core import (
    Group, Job, JobRule, Keyspace, KIND_ALONE, KIND_COMMON)
from cronsun_tpu.logsink import JobLogStore
from cronsun_tpu.node.agent import NodeAgent
from cronsun_tpu.sched import SchedulerService
from cronsun_tpu.store import MemStore

KS = Keyspace()


@pytest.fixture
def world():
    store = MemStore()
    sink = JobLogStore()
    agents = [NodeAgent(store, sink, node_id=f"node-{i}") for i in range(2)]
    for a in agents:
        a.register()
    sched = SchedulerService(store, job_capacity=256, node_capacity=64,
                             window_s=2)
    yield store, sink, sched, agents
    store.close()


def put_job(store, job: Job):
    job.check()
    store.put(KS.job_key(job.group, job.id), job.to_json())


def drive(sched, agents, t0, seconds):
    """Step the scheduler over [t0, t0+seconds), letting agents consume."""
    t = t0
    end = t0 + seconds
    while t < end:
        sched.step(now=t)
        for a in agents:
            a.poll()
        for a in agents:
            a.join_running()
        t = sched._next_epoch  # continue from where planning got to
    for a in agents:
        a.poll()
        a.join_running()


def test_common_job_runs_on_all_eligible_nodes(world):
    store, sink, sched, agents = world
    job = Job(name="hello", command="echo hi", kind=KIND_COMMON,
              rules=[JobRule(timer="* * * * * *",
                             nids=["node-0", "node-1"])])
    put_job(store, job)
    t0 = 1_753_000_000
    drive(sched, agents, t0, 3)
    logs, total = sink.query_logs(job_ids=[job.id])
    assert total >= 4  # >= 2 seconds x 2 nodes
    nodes = {l.node for l in logs}
    assert nodes == {"node-0", "node-1"}
    assert all(l.success for l in logs)


def test_alone_job_runs_on_exactly_one_node_per_second(world):
    store, sink, sched, agents = world
    job = Job(name="solo", command="echo solo", kind=KIND_ALONE,
              rules=[JobRule(timer="* * * * * *",
                             nids=["node-0", "node-1"])])
    put_job(store, job)
    drive(sched, agents, 1_753_000_100, 4)
    logs, total = sink.query_logs(job_ids=[job.id])
    # compressed synthetic time makes same-step seconds race the lifetime
    # lock, so some seconds legitimately skip — but at least one per step
    # runs, and runs never overlap
    assert total >= 2
    # exactly-one semantics: every execution is recorded by its own
    # (job, second) fence key — no fence without a run, no run twice
    locks = store.get_prefix(KS.lock + job.id + "/")
    assert len(locks) == total
    spans = sorted((l.begin_ts, l.end_ts) for l in logs)
    for (b1, e1), (b2, e2) in zip(spans, spans[1:]):
        assert b2 >= e1, "Alone executions overlapped"


def test_exclude_nids_subtractive(world):
    store, sink, sched, agents = world
    g = Group(id="all", name="all", node_ids=["node-0", "node-1"])
    store.put(KS.group_key(g.id), g.to_json())
    job = Job(name="excl", command="echo x", kind=KIND_COMMON,
              rules=[JobRule(timer="* * * * * *", gids=["all"],
                             exclude_nids=["node-1"])])
    put_job(store, job)
    drive(sched, agents, 1_753_000_200, 3)
    logs, total = sink.query_logs(job_ids=[job.id])
    assert total >= 1
    assert {l.node for l in logs} == {"node-0"}


def test_job_delete_stops_firing(world):
    store, sink, sched, agents = world
    job = Job(name="gone", command="echo gone", kind=KIND_COMMON,
              rules=[JobRule(timer="* * * * * *", nids=["node-0"])])
    put_job(store, job)
    drive(sched, agents, 1_753_000_300, 2)
    _, before = sink.query_logs(job_ids=[job.id])
    assert before >= 1
    store.delete(KS.job_key(job.group, job.id))
    drive(sched, agents, 1_753_000_310, 3)
    _, after = sink.query_logs(job_ids=[job.id])
    assert after == before


def test_pause_suppresses_firing(world):
    store, sink, sched, agents = world
    job = Job(name="paused", command="echo p", pause=True, kind=KIND_COMMON,
              rules=[JobRule(timer="* * * * * *", nids=["node-0"])])
    put_job(store, job)
    drive(sched, agents, 1_753_000_400, 3)
    _, total = sink.query_logs(job_ids=[job.id])
    assert total == 0


def test_once_trigger_runs_immediately(world):
    store, sink, sched, agents = world
    job = Job(name="manual", command="echo now", kind=KIND_COMMON,
              rules=[JobRule(timer="0 0 0 1 1 ?", nids=["node-0"])])
    put_job(store, job)
    store.put(KS.once_key(job.group, job.id), "node-1")  # explicit target
    for a in agents:
        a.poll()
        a.join_running()
    logs, total = sink.query_logs(job_ids=[job.id])
    assert total == 1 and logs[0].node == "node-1"


def test_failed_job_posts_notice(world):
    store, sink, sched, agents = world
    job = Job(name="failer", command="false", kind=KIND_COMMON,
              fail_notify=True, to=["ops@example.com"],
              rules=[JobRule(timer="* * * * * *", nids=["node-0"])])
    put_job(store, job)
    drive(sched, agents, 1_753_000_500, 2)
    logs, total = sink.query_logs(job_ids=[job.id], failed_only=True)
    assert total >= 1
    kv = store.get(KS.noticer_key("node-0"))
    assert kv is not None
    msg = json.loads(kv.value)
    assert "failer" in msg["subject"] and msg["to"] == ["ops@example.com"]


def test_node_death_reroutes_exclusive_job(world):
    store, sink, sched, agents = world
    job = Job(name="failover", command="echo f", kind=KIND_ALONE,
              rules=[JobRule(timer="* * * * * *",
                             nids=["node-0", "node-1"])])
    put_job(store, job)
    drive(sched, agents, 1_753_000_600, 2)
    agents[0].unregister()  # node-0 dies (lease revoked -> DELETE event)
    drive(sched, agents, 1_753_000_610, 3)
    logs, _ = sink.query_logs(job_ids=[job.id])
    late = [l for l in logs if l.begin_ts >= time.time() - 300]
    # all executions after the death that were dispatched to node-1
    assert any(l.node == "node-1" for l in logs)


def test_leader_election_single_leader(world):
    store, sink, sched, agents = world
    sched2 = SchedulerService(store, job_capacity=256, node_capacity=64,
                              node_id="scheduler-2")
    assert sched.try_lead()
    assert not sched2.try_lead()
    sched.stop()  # releases leadership
    assert sched2.try_lead()


def test_alone_lifetime_lock_serializes_across_agents(world):
    """A slow KindAlone job on a per-second timer: runs must be strictly
    serialized fleet-wide, skipped seconds while a run is live
    (reference job.go:87-123)."""
    store, sink, sched, agents = world
    job = Job(name="long-solo", command="sleep 0.4", kind=KIND_ALONE,
              rules=[JobRule(timer="* * * * * *",
                             nids=["node-0", "node-1"])])
    put_job(store, job)
    t0 = 1_753_000_700
    t = t0
    # do NOT join between steps: orders pile up while a run is live
    for _ in range(3):
        sched.step(now=t)
        for a in agents:
            a.poll()
        t = sched._next_epoch
        time.sleep(0.15)
    for a in agents:
        a.join_running(timeout=15)
    logs, total = sink.query_logs(job_ids=[job.id])
    assert total >= 1
    spans = sorted((l.begin_ts, l.end_ts) for l in logs)
    for (b1, e1), (b2, e2) in zip(spans, spans[1:]):
        assert b2 >= e1, "Alone executions overlapped fleet-wide"
    # fewer executions than planned seconds: overlapping fires were skipped
    assert total < 6
    # the lifetime lock is released after the last run completes
    assert store.get(KS.alone_lock_key(job.id)) is None


def test_avg_time_persisted_and_flows_to_planner_cost(world):
    store, sink, sched, agents = world
    job = Job(name="timed", command="sleep 0.3", kind=KIND_ALONE,
              rules=[JobRule(timer="* * * * * *", nids=["node-0"])])
    put_job(store, job)
    drive(sched, agents, 1_753_000_800, 2)
    kv = store.get(KS.job_key(job.group, job.id))
    stored = Job.from_json(kv.value)
    assert stored.avg_time >= 0.3, "measured runtime not persisted"
    # next step folds the watch event into the planner's cost column
    sched.step(now=1_753_000_900)
    row = sched.rows.by_cmd[(job.group, job.id, job.rules[0].id)]
    import numpy as np
    assert float(np.asarray(sched.planner.cost[row])) >= 0.3


def test_hwm_prevents_failover_redispatch(world):
    """A new leader resumes planning from the persisted high-water mark,
    so seconds the dead leader already dispatched don't re-fire Common
    jobs (which have no per-second fence)."""
    store, sink, sched, agents = world
    job = Job(name="once-only", command="echo x", kind=KIND_COMMON,
              rules=[JobRule(timer="* * * * * *", nids=["node-0"])])
    put_job(store, job)
    t0 = 1_753_001_000
    sched.step(now=t0)           # plans [t0+1, t0+2]
    hwm = sched._next_epoch
    sched.stop()                 # leader dies
    sched2 = SchedulerService(store, job_capacity=256, node_capacity=64,
                              window_s=2, node_id="scheduler-2")
    sched2.step(now=t0)          # same wall-clock instant
    # dispatch orders must cover each epoch at most once
    epochs = [int(kv.key.split("/")[4])
              for kv in store.get_prefix(KS.dispatch)]
    assert len(epochs) == len(set(epochs)), \
        f"epochs double-dispatched: {sorted(epochs)}"
    assert sched2._next_epoch == hwm + 2
    sched2.stop()


def test_outstanding_orders_reserve_capacity(world):
    """Dispatch orders not yet started still count against node capacity
    in reconcile_capacity (dispatch->spawn gap overcommit guard)."""
    store, sink, sched, agents = world
    job = Job(name="excl-res", command="echo r", kind=KIND_ALONE,
              rules=[JobRule(timer="* * * * * *", nids=["node-0"])])
    put_job(store, job)
    sched.node_caps["node-0"] = 2
    sched.drain_watches()
    sched._flush_device()
    # an outstanding order written by a (dead) leader, no agent
    # consuming.  The orders watch is delete-only (own publishes are
    # mirrored at submit), so FOREIGN orders reach the mirror via the
    # anti-entropy listing — kicked at leadership takeover — not via
    # watch; run it the way a takeover would.
    store.put(KS.dispatch_key("node-0", 1_753_001_100, job.group, job.id),
              "{}")
    sched._mirror_antientropy()
    sched.reconcile_capacity()
    import numpy as np
    col = sched.universe.index["node-0"]
    assert int(np.asarray(sched.planner.rem_cap[col])) == 1


def test_steady_state_step_issues_o_delta_store_ops():
    """With ~10k outstanding procs, steady-state step() must NOT re-list
    the proc/dispatch/alone prefixes — the watch-fed mirrors carry the
    state and only the periodic anti-entropy re-lists.  Pinned by
    counting get_prefix calls across steps inside the anti-entropy
    window."""
    store = MemStore()
    calls = []
    orig = store.get_prefix

    def counting_get_prefix(prefix):
        calls.append(prefix)
        return orig(prefix)
    store.get_prefix = counting_get_prefix

    clock_t = [1_753_002_000.0]
    sched = SchedulerService(store, job_capacity=256, node_capacity=64,
                             window_s=2, clock=lambda: clock_t[0])
    job = Job(name="busy", command="echo b", kind=KIND_ALONE,
              rules=[JobRule(timer="* * * * * *", nids=["node-0"])])
    put_job(store, job)
    store.put(KS.node_key("node-0"), "1")
    # ~10k outstanding proc keys land as one bulk write
    store.put_many([(KS.proc_key(f"n{i % 50}", job.group, job.id, str(i)),
                     "t") for i in range(10_000)])
    sched.step(now=int(clock_t[0]))          # absorb deltas via watch
    assert len(sched._procs) == 10_000       # mirror caught up
    calls.clear()
    for _ in range(5):                       # steady state, window intact
        clock_t[0] += 2
        sched.step(now=int(clock_t[0]))
    mirror_prefixes = [p for p in calls
                       if p.startswith((KS.proc, KS.dispatch, KS.lock))]
    assert mirror_prefixes == [], \
        f"steady-state step re-listed execution state: {mirror_prefixes}"
    # anti-entropy still runs once its interval elapses
    clock_t[0] += sched.mirror_resync_s + 1
    sched.step(now=int(clock_t[0]))
    assert any(p.startswith(KS.proc) for p in calls)
    sched.stop()
    store.close()


def test_mirror_tracks_lease_expiry():
    """A proc key expiring server-side (dead node) must leave the mirror
    via its watch DELETE — capacity frees without any re-list."""
    store = MemStore()
    store.start_sweeper(0.05)
    clock_t = [1_753_003_000.0]
    sched = SchedulerService(store, job_capacity=64, node_capacity=8,
                             window_s=2, clock=lambda: clock_t[0])
    lease = store.grant(0.3)
    store.put(KS.proc_key("nx", "g", "j", "1"), "t", lease=lease)
    sched.drain_watches()
    assert len(sched._procs) == 1
    deadline = time.time() + 5
    while sched._procs and time.time() < deadline:
        time.sleep(0.05)
        sched.drain_watches()
    assert not sched._procs, "expired proc never left the mirror"
    sched.stop()
    store.close()


def test_every_phase_survives_job_rewrite(world):
    """Toggling pause (or any rewrite with an unchanged timer) must not
    re-anchor an @every rule's phase."""
    store, sink, sched, agents = world
    job = Job(name="everyjob", command="echo e", kind=KIND_COMMON,
              rules=[JobRule(timer="@every 1h", nids=["node-0"])])
    put_job(store, job)
    sched.drain_watches()
    row = sched.rows.by_cmd[(job.group, job.id, job.rules[0].id)]
    phase1 = sched._table_updates[row]["phase_mod"]
    sched._flush_device()
    time.sleep(1.1)              # real clock advances across a second
    job.pause = True
    put_job(store, job)
    sched.drain_watches()
    phase2 = sched._table_updates[row]["phase_mod"]
    assert phase2 == phase1, "@every phase re-anchored by unrelated rewrite"
    assert sched._table_updates[row]["paused"]


def test_every_phase_survives_failover(world):
    """A new leader must reconstruct @every phases from the store, not
    re-anchor them at its own start time."""
    store, sink, sched, agents = world
    job = Job(name="everyfo", command="echo e", kind=KIND_COMMON,
              rules=[JobRule(timer="@every 1h", nids=["node-0"])])
    put_job(store, job)
    sched.drain_watches()
    row = sched.rows.by_cmd[(job.group, job.id, job.rules[0].id)]
    phase1 = sched._table_updates[row]["phase_mod"]
    sched.stop()
    time.sleep(1.1)
    sched2 = SchedulerService(store, job_capacity=256, node_capacity=64,
                              window_s=2, node_id="scheduler-2")
    row2 = sched2.rows.by_cmd[(job.group, job.id, job.rules[0].id)]
    phase2 = sched2._table_updates.get(row2)
    if phase2 is None:   # already flushed during _load_initial
        import numpy as np
        phase2 = {"phase_mod": int(np.asarray(
            sched2.planner.table.phase_mod[row2]))}
    assert phase2["phase_mod"] == phase1, \
        "@every phase re-anchored on failover"
    sched2.stop()


def test_scheduler_service_over_sharded_planner():
    """The production service runs unchanged over a mesh-sharded planner
    (cronsun-sched --mesh D): watch->delta row setters, capacity
    reconciliation, windowed planning, dispatch — end-to-end to a real
    execution on the 8-device virtual mesh."""
    import jax
    from cronsun_tpu.parallel.mesh import ShardedTickPlanner, make_mesh
    assert len(jax.devices()) >= 8
    store = MemStore()
    sink = JobLogStore()
    agents = [NodeAgent(store, sink, node_id=f"mesh-n{i}")
              for i in range(2)]
    for a in agents:
        a.register()
    planner = ShardedTickPlanner(make_mesh(8), job_capacity=2048,
                                 node_capacity=64, impl="jnp",
                                 max_fire_bucket=2048)
    sched = SchedulerService(store, job_capacity=2048, node_capacity=64,
                             window_s=2, planner=planner)
    job = Job(name="mesh-job", command="echo sharded", kind=KIND_COMMON,
              rules=[JobRule(timer="* * * * * *",
                             nids=["mesh-n0", "mesh-n1"])])
    put_job(store, job)
    alone = Job(name="mesh-alone", command="echo one", kind=KIND_ALONE,
                rules=[JobRule(timer="* * * * * *",
                               nids=["mesh-n0", "mesh-n1"])])
    put_job(store, alone)
    t0 = 1_753_000_000
    drive(sched, agents, t0, 4)
    logs, total = sink.query_logs()
    by_name = {}
    for l in logs:
        by_name.setdefault(l.name, []).append(l)
    # Common ran on both nodes every second
    assert len(by_name.get("mesh-job", [])) >= 4
    assert {l.node for l in by_name["mesh-job"]} == {"mesh-n0", "mesh-n1"}
    # Alone ran exactly once per planned second, never concurrently
    assert by_name.get("mesh-alone"), "alone job never ran"
    assert all(l.success for l in logs)
    store.close()


def test_scheduler_resync_after_watch_loss(world):
    """A lost watch stream (overflow) must not silently stall the
    scheduler: drain_watches resynchronizes — new jobs appear, deleted
    jobs drop — from the store's current contents."""
    store, sink, sched, agents = world
    j1 = Job(name="pre", command="echo 1", kind=KIND_COMMON,
             rules=[JobRule(timer="* * * * * *", nids=["node-0"])])
    put_job(store, j1)
    sched.drain_watches()
    assert ("default", j1.id) in sched.rows.by_job
    # cripple the jobs watcher and blast it past its backlog
    sched._w_jobs._max_backlog = 5
    store.delete(KS.job_key("default", j1.id))
    j2 = Job(name="post", command="echo 2", kind=KIND_COMMON,
             rules=[JobRule(timer="* * * * * *", nids=["node-0"])])
    put_job(store, j2)
    for i in range(10):
        store.put(KS.cmd + f"filler/f{i}", "not-json")
    sched.drain_watches()      # sees the buffered tail
    sched.drain_watches()      # hits WatchLost -> resync
    assert ("default", j1.id) not in sched.rows.by_job, \
        "deleted job survived resync"
    assert ("default", j2.id) in sched.rows.by_job, \
        "new job missed by resync"


def test_resync_reload_takes_registered_jobs_one_at_a_time():
    """The cold load writes jobs it has not seen a column at a time; a
    reload over a registry that already holds them (what resync runs)
    hands each to _apply_job, and leaves the scheduler as it was."""
    store = MemStore()
    store.put(KS.node_key("node-0"), "x")
    for i in range(6):
        put_job(store, Job(
            name=f"j{i}", command="true",
            kind=KIND_COMMON if i % 2 else KIND_ALONE,
            rules=[JobRule(timer="@every 30s" if i % 3 else "*/5 * * * * *",
                           nids=["node-0"])]))
    sched = SchedulerService(store, job_capacity=64, node_capacity=16,
                             window_s=2)
    assert sched.stats["cold_jobs_columnar_total"] == 6
    assert sched.stats["cold_jobs_per_job_total"] == 0

    def state():
        return (dict(sched.jobs), dict(sched.rows.by_cmd),
                dict(sched._row_phase), dict(sched._row_dispatch),
                sched.builder.matrix.tobytes(), sched._rd_flags.tobytes(),
                sched._rd_tbase.tobytes(), sched._rd_sbase.tobytes(),
                [(kv.key, kv.value) for kv in store.get_prefix(KS.phase)])
    before = state()
    sched._load_initial()
    assert sched.stats["cold_jobs_per_job_total"] == 6
    assert sched.stats["cold_jobs_columnar_total"] == 6
    assert state() == before


def test_agent_resync_after_watch_loss():
    """An agent whose dispatch watch overflows re-lists still-live orders
    and runs them exactly once (store fence); Common broadcasts dedupe
    via the in-memory (job, second) guard."""
    store, sink = MemStore(), JobLogStore()
    agent = NodeAgent(store, sink, node_id="rz")
    agent.register()
    job = Job(name="rz-job", command="echo rz", kind=KIND_COMMON,
              rules=[JobRule(timer="* * * * * *", nids=["rz"])])
    put_job(store, job)
    epoch = int(time.time()) - 1
    agent._w_dispatch._max_backlog = 2
    for i in range(6):   # overflow the dispatch watch with junk keys
        store.put(KS.dispatch + f"rz/junk-{i}", "{}")
    # the real order we must not lose
    store.put(KS.dispatch_key("rz", epoch, job.group, job.id), "{}")
    agent.poll()               # buffered tail
    agent.poll()               # WatchLost -> resync re-lists + runs
    agent.join_running(timeout=30)
    _, total = sink.query_logs(job_ids=[job.id])
    assert total >= 1, "order lost across watch overflow"
    store.close()



def _overflow_world(prefix, n_jobs=2600):
    """Store + planner + scheduler with more same-second exclusive fires
    than the 2048 bucket floor — shared by the overflow tests so the
    burst configuration can't silently diverge between them."""
    from cronsun_tpu.ops.planner import TickPlanner

    store = MemStore()
    store.put(KS.node_key("n0"), "host:1")
    for i in range(n_jobs):
        job = Job(id=f"{prefix}{i:04d}", name=f"{prefix}{i}", group="g",
                  command="true", kind=2,
                  rules=[JobRule(id="r", timer="* * * * * *",
                                 nids=["n0"])])
        store.put(KS.job_key("g", job.id), job.to_json())
    planner = TickPlanner(job_capacity=4096, node_capacity=32,
                          max_fire_bucket=2048)
    sched = SchedulerService(store, planner=planner, window_s=1,
                             node_capacity=32)
    return store, sched, n_jobs


def test_overflow_becomes_late_fires_never_drops():
    """A second whose fire count exceeds the adaptive bucket is
    re-planned with an escalated bucket: every fire dispatches (late),
    overflow_late_fires counts them, and nothing lands in
    overflow_drops (VERDICT r3 #2; reference contract: fires late,
    never never — cron.go:212-215)."""
    store, sched, n_jobs = _overflow_world("of")
    t0 = 1_753_000_000
    sched.step(now=t0)       # burst second truncated to the bucket; the
                             # full set re-plans ASYNC on the device
    sched.step(now=t0 + 1)   # matured replan publishes every fire
    epoch = t0 + 1
    # coalesced format: ONE (node, second) key whose value is the job
    # list; the truncated head's re-publish OVERWRITES the bundle, so
    # the full fire set is what agents see — never duplicate keys
    kv = store.get(KS.dispatch_bundle_key("n0", epoch))
    assert kv is not None, "coalesced order bundle missing"
    assert len(json.loads(kv.value)) == n_jobs
    assert sched.stats["overflow_late_fires"] >= n_jobs - 2048
    assert sched.stats["overflow_drops"] == 0
    assert sched.metrics_snapshot()["overflow_late_fires_total"] > 0
    store.close()


def test_publish_hole_rewinds_plan_cursor():
    """A window whose publish ultimately fails must NOT be skipped: the
    publisher stops advancing the HWM at the hole and the next step
    rewinds its cursor there and re-plans (late, never lost) — the
    write-then-mark contract survives the async publisher."""
    store = MemStore()
    sink = JobLogStore()
    sched = SchedulerService(store, job_capacity=256, node_capacity=64,
                             window_s=2, node_id="hole-sched")
    agent = NodeAgent(store, sink, node_id="hole-n0")
    agent.register()
    job = Job(name="hole", command="echo h", kind=0,
              rules=[JobRule(id="r", timer="* * * * * *",
                             nids=["hole-n0"])])
    job.check()
    store.put(KS.job_key(job.group, job.id), job.to_json())
    t0 = 1_753_900_000
    assert sched.step(now=t0) > 0          # plans [t0+1, t0+2]

    # wedge the publisher's store path: every put_many fails
    real_put_many = store.put_many
    fails = {"n": 0}

    def broken(items, lease=0):
        fails["n"] += 1
        raise RuntimeError("store down")
    # MemStore has no clone(), so the publisher's single lane IS this
    # store object — replacing put_many wedges the publish path
    assert sched._owned_lanes == []
    store.put_many = broken
    sched.step(now=t0 + 2)                 # window [t0+3, t0+4] fails
    sched.publisher.flush()
    assert fails["n"] >= 4, "publisher should have retried"
    store.put_many = real_put_many

    # the cursor must rewind to the hole and republish those seconds
    n = sched.step(now=t0 + 4)
    sched.publisher.flush()
    keys = [kv.key for kv in store.get_prefix(KS.dispatch_all)]
    missed = [k for k in keys if f"/{t0 + 3}/" in k]
    assert missed, f"epoch {t0+3} never re-published (orders: {keys})"
    assert sched.stats["skipped_seconds"] == 0
    agent.stop()
    sched.stop()
    store.close()


def test_pending_replans_drain_on_stop():
    """An async overflow replan still in flight when the leader stops
    must be gathered and PUBLISHED on the way out — its tail fires were
    already counted as late, and abandoning the handle would turn late
    into lost."""
    store, sched, n_jobs = _overflow_world("dr")
    t0 = 1_753_910_000
    sched.step(now=t0)       # truncated head published; replan pending
    assert sched._pending_replans, "overflow replan should be pending"
    sched.stop()             # drains the replan, then the publisher
    epoch = t0 + 1
    kv = store.get(KS.dispatch_bundle_key("n0", epoch))
    n_fires = len(json.loads(kv.value)) if kv is not None else 0
    assert n_fires == n_jobs, \
        f"stop() dropped replan fires ({n_fires}/{n_jobs})"
    assert sched.stats["overflow_drops"] == 0
    store.close()


def test_exclusive_orders_coalesce_per_node_second():
    """The wire-format contract: N exclusive fires targeting one node in
    one second publish ONE (node, second) key whose value lists every
    job — and the leader's own mirror reserves len(jobs) slots against
    that node until the key is consumed."""
    store = MemStore()
    store.put(KS.node_key("cz0"), "host:1")
    n = 5
    for i in range(n):
        job = Job(id=f"cz{i:02d}", name=f"cz{i}", group="g",
                  command="true", kind=2,
                  rules=[JobRule(id="r", timer="* * * * * *",
                                 nids=["cz0"])])
        store.put(KS.job_key("g", job.id), job.to_json())
    sched = SchedulerService(store, job_capacity=64, node_capacity=8,
                             window_s=2, node_id="cz-sched")
    t0 = 1_753_700_000
    sched.step(now=t0)
    keys = [kv for kv in store.get_prefix(KS.dispatch)
            if not kv.key.startswith(KS.dispatch_all)]
    # one key per (node, second) — the window is 2 s, so exactly 2 keys
    assert len(keys) == 2, [kv.key for kv in keys]
    for kv in keys:
        entries = json.loads(kv.value)
        assert sorted(entries) == sorted(f"g/cz{i:02d}" for i in range(n))
    # capacity reservation: the mirror holds len(jobs) slots per key
    assert sched._excl_cnt.get("cz0") == 2 * n
    # herd gauges: exclusive keys per second bounded by nodes (1), while
    # the fires they carry count separately
    assert sched.max_second_node_keys == 1
    assert sched.max_second_excl_fires == n
    # consuming one bundle releases its whole reservation via the
    # delete-only orders watch
    store.delete(keys[0].key)
    sched.drain_watches()
    assert sched._excl_cnt.get("cz0") == n
    sched.stop()
    store.close()


def test_coalesced_bundle_reserves_capacity_via_antientropy():
    """A FOREIGN coalesced order (written by a dead leader) reaches the
    mirror via the anti-entropy listing and reserves len(jobs) slots —
    reconcile_capacity subtracts them from the node's device capacity
    exactly as the legacy per-job keys did."""
    store = MemStore()
    sink = JobLogStore()
    agent = NodeAgent(store, sink, node_id="rv0")
    agent.register()
    sched = SchedulerService(store, job_capacity=64, node_capacity=8,
                             window_s=2, node_id="rv-sched")
    for i in range(2):
        job = Job(id=f"rv{i}", name=f"rv{i}", group="g", command="true",
                  kind=2,
                  rules=[JobRule(id="r", timer="0 0 0 1 1 *",
                                 nids=["rv0"])])
        job.check()
        store.put(KS.job_key("g", job.id), job.to_json())
    sched.node_caps["rv0"] = 3
    sched.drain_watches()
    sched._flush_device()
    store.put(KS.dispatch_bundle_key("rv0", 1_753_800_000),
              json.dumps(["g/rv0", "g/rv1"]))
    sched._mirror_antientropy()
    sched.reconcile_capacity()
    import numpy as np
    col = sched.universe.index["rv0"]
    assert int(np.asarray(sched.planner.rem_cap[col])) == 1
    agent.stop()
    sched.stop()
    store.close()


def test_publish_hole_rewind_republishes_coalesced_bundles():
    """The hole-rewind contract over the NEW wire format: a window whose
    publish fails is re-planned after the store heals, and the missed
    second's EXCLUSIVE fires come back as a coalesced (node, second)
    bundle (late, never lost)."""
    store = MemStore()
    store.put(KS.node_key("hb0"), "host:1")
    job = Job(id="hb", name="hb", group="g", command="true", kind=2,
              rules=[JobRule(id="r", timer="* * * * * *", nids=["hb0"])])
    store.put(KS.job_key("g", "hb"), job.to_json())
    sched = SchedulerService(store, job_capacity=64, node_capacity=8,
                             window_s=2, node_id="hb-sched")
    t0 = 1_753_910_000
    assert sched.step(now=t0) > 0
    real_put_many = store.put_many

    def broken(items, lease=0):
        raise RuntimeError("store down")
    assert sched._owned_lanes == []
    store.put_many = broken
    sched.step(now=t0 + 2)                 # window [t0+3, t0+4] fails
    sched.publisher.flush()
    store.put_many = real_put_many
    sched.step(now=t0 + 4)                 # rewinds to the hole
    sched.publisher.flush()
    kv = store.get(KS.dispatch_bundle_key("hb0", t0 + 3))
    assert kv is not None, "missed second's bundle never re-published"
    assert json.loads(kv.value) == ["g/hb"]
    assert sched.stats["skipped_seconds"] == 0
    assert sched.metrics_snapshot()["publish_abandoned"] >= 0
    sched.stop()
    store.close()


def test_publish_hole_older_than_catchup_clears_not_livelocks():
    """ADVICE r5 high — the publish-hole livelock: when the hole epoch
    ages past max_catchup_s, the catch-up clamp moves the cursor PAST
    the hole; the hole must then be CLEARED (its seconds counted as
    skipped) or every later window is abandoned forever.  After the
    clamp, publishing must resume and the abandoned windows must be
    visible in the metrics snapshot."""
    store = MemStore()
    store.put(KS.node_key("lv0"), "host:1")
    job = Job(id="lv", name="lv", group="g", command="true", kind=2,
              rules=[JobRule(id="r", timer="* * * * * *", nids=["lv0"])])
    store.put(KS.job_key("g", "lv"), job.to_json())
    sched = SchedulerService(store, job_capacity=64, node_capacity=8,
                             window_s=2, node_id="lv-sched")
    sched.max_catchup_s = 10
    t0 = 1_753_920_000
    assert sched.step(now=t0) > 0
    real_put_many = store.put_many

    def broken(items, lease=0):
        raise RuntimeError("store down")
    assert sched._owned_lanes == []
    store.put_many = broken
    sched.step(now=t0 + 2)                 # hole at t0+3
    sched.publisher.flush()
    assert sched.publisher.take_failed_epoch() is not None
    # store heals only AFTER the hole aged past the catch-up horizon;
    # meanwhile a window queued BEHIND the hole (the async-publisher
    # race: submitted before the step observed the failure) is abandoned
    # — and that abandonment must be countable from metrics alone
    sched.publisher.submit([(t0 + 7, [("k", "v")])], 0, 0,
                           covers_from=t0 + 7)
    sched.publisher.flush()
    assert sched.publisher.stats["publish_abandoned"] >= 1
    sched.step(now=t0 + 6)
    sched.publisher.flush()
    store.put_many = real_put_many
    t_late = t0 + 3 + sched.max_catchup_s + 5
    sched.step(now=t_late)                 # clamp passes the hole
    sched.publisher.flush()
    assert sched.publisher.take_failed_epoch() is None, \
        "aged-out hole never cleared (livelock)"
    assert sched.stats["skipped_seconds"] > 0, \
        "the hole's seconds must be counted as skipped"
    # dispatch RESUMES: the clamped window re-plans from the catch-up
    # horizon (now+1-max_catchup_s), so bundles for seconds BEYOND the
    # failed window land in the store again
    fresh = [kv.key for kv in store.get_prefix(KS.dispatch)
             if not kv.key.startswith(KS.dispatch_all)
             and int(kv.key.split("/")[4]) > t0 + 4]
    assert fresh, "dispatch never resumed after the hole aged out"
    snap = sched.metrics_snapshot()
    assert snap["publish_abandoned"] >= 1, \
        "hole episode invisible in metrics"
    sched.stop()
    store.close()
