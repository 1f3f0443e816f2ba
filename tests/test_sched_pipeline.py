"""The pipelined scheduler step: overlap invariants + vectorized build.

The step is a two-stage pipeline (step thread: drain/reconcile/flush/
dispatch; build worker: gather/build/submit -> publisher).  These tests
pin the invariants the overlap must not break — exactly-once under
duplicate delivery, no second reordering under backpressure (the step
STALLS instead), hole/rewind while an overlapped window is in flight —
plus the vectorized ``_build_plan_orders``'s byte-identity with the
per-fire loop it replaced, and a CPU smoke bench that fails tier-1 if
the pipeline regresses to the serial path.
"""

import json
import time

import numpy as np
import pytest

from cronsun_tpu.core import Job, JobRule, Keyspace
from cronsun_tpu.logsink import JobLogStore
from cronsun_tpu.node.agent import NodeAgent
from cronsun_tpu.ops.planner import TickPlan
from cronsun_tpu.sched import SchedulerService
from cronsun_tpu.store import MemStore

KS = Keyspace()


def put_job(store, job: Job):
    job.check()
    store.put(KS.job_key(job.group, job.id), job.to_json())


def _flush(sched):
    sched._builder.flush()
    sched.publisher.flush()
    sched._drain_build_acct()


# ---------------------------------------------------------------------------
# differential: vectorized build == per-fire loop, byte for byte
# ---------------------------------------------------------------------------

def test_vectorized_build_byte_identical_on_randomized_plans():
    """The vectorized group-by-node order build must produce EXACTLY the
    retired loop's output — same (key, value) tuples in the same order,
    same accounting, same fire count — across randomized plans mixing
    valid/stale rows, Common/exclusive/Alone kinds, live/dead/out-of-
    range node columns, and duplicate fires."""
    store = MemStore()
    for i in range(5):
        store.put(KS.node_key(f"dn{i}"), "host:1")
    # mixed population: Common (0), Alone (1), exclusive Interval (2);
    # one id exercising the non-wire-safe json.dumps payload path
    for i in range(24):
        kind = (0, 1, 2, 2)[i % 4]
        job = Job(id=f"vj{i:02d}", name=f"v{i}", group="g",
                  command="true", kind=kind,
                  rules=[JobRule(id="r" if i % 3 else "r~%d" % i,
                                 timer="* * * * * *",
                                 nids=[f"dn{i % 5}"])])
        store.put(KS.job_key("g", job.id), job.to_json())
    sched = SchedulerService(store, job_capacity=64, node_capacity=8,
                             window_s=1, node_id="vec-sched")
    # one Alone job's lifetime lock is LIVE: its fires are ordered all
    # the same (the node judges the lock at the fire's second)
    store.put(KS.alone_lock_key("vj01"), "held")
    # one node dies: its column must route to nothing
    store.delete(KS.node_key("dn3"))
    sched.drain_watches()
    J, N = sched.planner.J, sched.planner.N
    rng = np.random.default_rng(7)
    rows_pool = np.arange(J)     # includes rows with no dispatch entry
    vj01 = (sched.rows.by_cmd[("g", "vj01", "r")],
            sched.universe.index["dn1"])
    for trial in range(25):
        f = int(rng.integers(0, 70))
        fired = rng.choice(rows_pool, size=f, replace=True)
        assigned = rng.integers(-2, N + 3, size=f)
        if trial == 0:      # the locked job fires, placed on its node
            fired, assigned = np.append(fired, vj01[0]), \
                np.append(assigned, vj01[1])
        plan = TickPlan(epoch_s=1_753_940_000 + trial,
                        fired=np.asarray(fired, np.int32),
                        assigned=np.asarray(assigned, np.int32),
                        overflow=0)
        sec_v, acct_v = [], []
        n_v = sched._build_plan_orders(plan, sec_v, acct_v)
        sec_r, acct_r = [], []
        n_r = sched._build_plan_orders_ref(plan, sec_r, acct_r)
        assert sec_v == sec_r, f"trial {trial}: orders diverged"
        assert acct_v == acct_r, f"trial {trial}: accounting diverged"
        assert n_v == n_r, f"trial {trial}: fire count diverged"
        if trial == 0:
            for sec in (sec_v, sec_r):
                assert any("/dn1/" in k and '"g/vj01"' in v
                           for _s, orders in sec for k, v in orders), \
                    "the Alone fire behind a live lock was left out"
    sched.stop()
    store.close()


# ---------------------------------------------------------------------------
# exactly-once under overlapped build/publish
# ---------------------------------------------------------------------------

def test_exactly_once_under_overlapped_publish():
    """With the build+publish stage overlapped (async pipeline), every
    exclusive (job, second) still executes exactly once — and a
    DUPLICATE bundle delivery for an already-claimed second is absorbed
    by the fences, never re-executed."""
    store = MemStore()
    sink = JobLogStore()
    agents = [NodeAgent(store, sink, node_id=f"px{i}") for i in range(2)]
    for a in agents:
        a.register()
    jobs = []
    for i in range(3):
        job = Job(id=f"pj{i}", name=f"p{i}", group="g", command="true",
                  kind=2,
                  rules=[JobRule(id="r", timer="* * * * * *",
                                 nids=["px0", "px1"])])
        put_job(store, job)
        jobs.append(job)
    sched = SchedulerService(store, job_capacity=256, node_capacity=64,
                             window_s=2, sync_publish=False,
                             node_id="px-sched")
    assert sched.pipelined
    t = 1_753_950_000
    for _ in range(3):
        sched.step(now=t)
        t = sched._next_epoch
    _flush(sched)
    for a in agents:
        a.poll()
        a.join_running(timeout=30)
    logs, total = sink.query_logs()
    assert total >= 6, "pipelined windows never executed"
    # exactly-once: one fence per execution, per job
    fences = 0
    for job in jobs:
        locks = store.get_prefix(KS.lock + job.id + "/")
        _, n = sink.query_logs(job_ids=[job.id])
        assert len(locks) == n, f"{job.id}: fences {len(locks)} != runs {n}"
        fences += len(locks)
    assert fences == total
    # duplicate delivery: re-publish a consumed bundle for a second that
    # already ran — the fences must win even though the pipeline would
    # happily overwrite/redeliver
    kv0 = store.get_prefix(KS.lock + jobs[0].id + "/")[0]
    epoch = int(kv0.key.rsplit("/", 1)[1])
    store.put(KS.dispatch_bundle_key("px0", epoch),
              json.dumps([f"g/{j.id}" for j in jobs]))
    store.put(KS.dispatch_bundle_key("px1", epoch),
              json.dumps([f"g/{j.id}" for j in jobs]))
    for a in agents:
        a.poll()
        a.join_running(timeout=30)
    _, total2 = sink.query_logs()
    assert total2 == total, "duplicate bundle delivery re-executed"
    for a in agents:
        a.stop()
    sched.stop()
    store.close()


# ---------------------------------------------------------------------------
# backpressure: the step stalls; seconds never reorder
# ---------------------------------------------------------------------------

def test_publisher_backpressure_stalls_step_without_reordering():
    """When the publish plane is slow, the builder's depth cap blocks
    the STEP (pipeline_stalls_total grows) rather than queueing plans
    unboundedly — and the published seconds still land oldest-first."""
    store = MemStore()
    store.put(KS.node_key("bp0"), "host:1")
    job = Job(id="bp", name="bp", group="g", command="true", kind=2,
              rules=[JobRule(id="r", timer="* * * * * *", nids=["bp0"])])
    store.put(KS.job_key("g", "bp"), job.to_json())
    sched = SchedulerService(store, job_capacity=64, node_capacity=8,
                             window_s=1, sync_publish=False,
                             node_id="bp-sched")
    real_put_many = store.put_many
    published_epochs = []

    def slow(items, lease=0):
        for k, _v in items:
            published_epochs.append(int(k.rsplit("/", 1)[1]))
        time.sleep(0.05)
        return real_put_many(items, lease=lease)
    store.put_many = slow
    t = 1_753_960_000
    for _ in range(8):
        sched.step(now=t)
        t = sched._next_epoch
    _flush(sched)
    snap = sched.metrics_snapshot()
    assert snap["pipeline_stalls_total"] >= 1, \
        "slow publisher never stalled the step"
    assert snap["pipeline_stall_ms_total"] > 0
    assert snap["publish_failures"] == 0
    assert published_epochs == sorted(published_epochs), \
        f"seconds reordered: {published_epochs}"
    assert len(set(published_epochs)) == len(published_epochs)
    store.put_many = real_put_many
    sched.stop()
    store.close()


# ---------------------------------------------------------------------------
# hole/rewind while an overlapped window is in flight
# ---------------------------------------------------------------------------

def test_hole_rewind_with_overlapped_window_in_flight():
    """A publish hole opened while a LATER window is already built and
    queued behind it (the overlap race): the queued window must be
    abandoned (never published past the hole), the cursor must rewind,
    and every second — the hole's and the abandoned window's — must be
    re-published.  Late, never lost, and the HWM never passes an
    unpublished second."""
    store = MemStore()
    store.put(KS.node_key("hv0"), "host:1")
    job = Job(id="hv", name="hv", group="g", command="true", kind=2,
              rules=[JobRule(id="r", timer="* * * * * *", nids=["hv0"])])
    store.put(KS.job_key("g", "hv"), job.to_json())
    sched = SchedulerService(store, job_capacity=64, node_capacity=8,
                             window_s=2, sync_publish=False,
                             node_id="hv-sched")
    t0 = 1_753_970_000
    sched.step(now=t0)                     # [t0+1, t0+2]
    _flush(sched)
    real_put_many = store.put_many

    def broken(items, lease=0):
        raise RuntimeError("store down")
    store.put_many = broken
    sched.step(now=t0 + 2)                 # [t0+3, t0+4] -> will fail
    sched.step(now=t0 + 4)                 # [t0+5, t0+6] overlapped,
    _flush(sched)                          # queued behind the hole
    assert sched.publisher.take_failed_epoch() == t0 + 3
    assert sched.publisher.stats["publish_abandoned"] >= 1, \
        "overlapped window behind the hole was not abandoned"
    store.put_many = real_put_many
    sched.step(now=t0 + 6)                 # rewinds to t0+3
    _flush(sched)
    sched.step(now=t0 + 6)                 # continues [t0+5, t0+6]
    _flush(sched)
    for ep in range(t0 + 3, t0 + 7):
        assert store.get(KS.dispatch_bundle_key("hv0", ep)) is not None, \
            f"second {ep - t0} never re-published after the rewind"
    assert sched.stats["skipped_seconds"] == 0
    hwm = store.get(KS.hwm)
    assert hwm is not None and int(hwm.value) >= t0 + 7
    sched.stop()
    store.close()


# ---------------------------------------------------------------------------
# pipelined -> serial toggle with a replan Future in flight
# ---------------------------------------------------------------------------

def test_serial_step_resolves_pipelined_replan_futures():
    """Toggling pipelined -> serial (the bench baseline / rollback
    switch) while an overflow replan is still pending as a dispatch
    FUTURE: the serial step must resolve and gather it — the replan's
    fires stay late, never lost."""
    from cronsun_tpu.ops.planner import TickPlanner
    store = MemStore()
    store.put(KS.node_key("tg0"), "host:1")
    n_jobs = 2600                  # > the 2048 bucket floor
    for i in range(n_jobs):
        job = Job(id=f"tg{i:04d}", name=f"tg{i}", group="g",
                  command="true", kind=2,
                  rules=[JobRule(id="r", timer="* * * * * *",
                                 nids=["tg0"])])
        store.put(KS.job_key("g", job.id), job.to_json())
    planner = TickPlanner(job_capacity=4096, node_capacity=32,
                          max_fire_bucket=2048)
    sched = SchedulerService(store, planner=planner, window_s=1,
                             node_capacity=32)
    t0 = 1_753_980_000
    sched.step(now=t0)             # burst truncated; replan request is
                                   # drained into a dispatch FUTURE
    assert sched._pending_replans, "overflow replan should be pending"
    sched.pipelined = False
    sched.step(now=t0 + 1)         # serial step gathers the Future
    sched.publisher.flush()
    kv = store.get(KS.dispatch_bundle_key("tg0", t0 + 1))
    assert kv is not None and len(json.loads(kv.value)) == n_jobs, \
        "replan fires lost across the pipelined->serial toggle"
    assert sched.stats["overflow_drops"] == 0
    sched.stop()
    store.close()


# ---------------------------------------------------------------------------
# CI smoke: a small pipelined bench config must show real overlap
# ---------------------------------------------------------------------------

def test_stop_during_a_long_step_abdicates_first_and_tears_down_last():
    """SIGTERM while a step is in flight: leadership is released at
    once (a successor takes over while the step drains), and the planes
    under the step are torn down only after it has run to its end — a
    step thread still inside the runtime at teardown is what used to
    abort the process."""
    import threading
    store = MemStore()
    sched = SchedulerService(store, job_capacity=64, node_capacity=8,
                             window_s=1, node_id="stop-sched")
    assert sched.try_lead()
    leader_key = sched._leader_key
    assert store.get(leader_key) is not None
    order, in_step, release = [], threading.Event(), threading.Event()

    def long_step():
        in_step.set()
        release.wait(10)
        order.append("step ends")
    sched.step = long_step
    real_stop = sched.publisher.stop
    sched.publisher.stop = lambda: (order.append("teardown"), real_stop())
    sched.start()
    assert in_step.wait(5)
    stopper = threading.Thread(target=sched.stop)
    stopper.start()
    deadline = time.monotonic() + 5
    while store.get(leader_key) is not None and time.monotonic() < deadline:
        time.sleep(0.02)
    assert store.get(leader_key) is None, \
        "leadership held while the in-flight step drains"
    assert stopper.is_alive() and order == [], \
        "teardown began under the running step"
    release.set()
    stopper.join(10)
    assert not stopper.is_alive()
    assert order == ["step ends", "teardown"]
    assert sched._thread is None and not sched.is_leader
    store.close()


def test_bench_seed_takes_a_seed_and_a_command():
    """chip_smoke.py's use of the one seeder: the generator follows
    ``seed`` and the command is the caller's."""
    from cronsun_tpu.store import MemStore
    from scripts.bench_sched import seed

    def docs(**kw):
        store = MemStore()
        seed(store, KS, 200, 16, on_log=lambda *a: None, **kw)
        assert len(store.get_prefix(KS.node)) == 16
        return {kv.key: kv.value for kv in store.get_prefix(KS.cmd)}
    cmd = "printenv CRONSUN_SCHEDULED_TS"
    a, b, c = docs(seed=3, command=cmd), docs(seed=3, command=cmd), \
        docs(seed=4, command=cmd)
    assert all(f'"command":"{cmd}"' in v for v in a.values())
    assert a == b and a != c and len(a) == 200
    assert all('"command":"true"' in v for v in docs().values())


def test_pipeline_smoke_bench_cpu():
    """Tier-1 regression tripwire for the pipeline itself: a small-scale
    pipelined bench config (networked py store, bench seed mix, paced
    steps) must show pipeline_overlap_ratio > 0 with zero publish
    failures — a silent fall-back to the serial path fails here."""
    from cronsun_tpu.store.remote import RemoteStore, StoreServer
    from scripts.bench_sched import seed

    srv = StoreServer().start()
    store = RemoteStore(srv.host, srv.port, timeout=60)
    try:
        seed(store, KS, 1200, 16, on_log=lambda *a: None)
        svc = SchedulerService(store, job_capacity=1200,
                               node_capacity=16, window_s=2,
                               dispatch_ttl=600.0, node_id="smoke-sched")
        assert svc.pipelined, "networked store must default to pipelined"
        assert not svc.sync_publish
        svc.step()                  # first step pays the XLA compile
        svc._builder.flush()
        svc.reset_latency_stats()
        for _ in range(4):
            svc.step()
            svc._builder.flush()    # paced, like the production loop
        svc.publisher.flush()
        svc._drain_build_acct()
        snap = svc.metrics_snapshot()
        assert snap["pipelined"] == 1
        assert snap["pipeline_overlap_ratio"] > 0, snap
        assert snap["publish_failures"] == 0, snap
        assert snap["pipeline_offstep_ms_total"] > 0
        svc.stop()
    finally:
        store.close()
        srv.stop()


def test_hwm_advance_retries_failed_write_before_flush_reports_done():
    """flush()'s contract is 'the latest landed HWM mark is WRITTEN'.
    A failed _advance_hwm must therefore keep retrying (not be marked
    done and silently dropped) — otherwise a kill drill right after a
    store blip restores from a mark that never landed."""
    from cronsun_tpu.sched.publisher import OrderPublisher

    class Lane:
        def put_many(self, chunk, lease=0):
            pass

    landed = []
    fails = [2]                       # first two advances blow up

    def advance(v):
        if fails[0] > 0:
            fails[0] -= 1
            raise RuntimeError("store blip")
        landed.append(v)

    pub = OrderPublisher([Lane()], advance)
    try:
        pub.submit([(100, [("k", "v")])], lease=0, hwm=100)
        # flush must block through both failures (0.5 s retry pacing)
        # and only report True once the mark actually landed
        assert pub.flush(timeout=10.0)
        assert len(landed) == 1 and landed[0] >= 100
    finally:
        pub.stop(timeout=5.0)
