"""The scheduler's one span primitive (``metrics.Spans``) and what
rides on it: the step-thread spans tile ``step()``, every name is one
``benchmarks/tracereduce.py`` takes as it is, the spans of one window
share its ``w`` across four threads on the profiler's own timeline, a
holder without a factory costs no JAX import, the cold-load phases
tile the start-up, and the counters of what the order build drops
silently count one per dropped fire at each of their sites.
"""

import collections
import re
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from cronsun_tpu.core import Job, JobRule, Keyspace
from cronsun_tpu.metrics import PhaseClock, Spans, process_age_s
from cronsun_tpu.ops.planner import TickPlan
from cronsun_tpu.sched import SchedulerService
from cronsun_tpu.sched import service as service_mod
from cronsun_tpu.store import MemStore

KS = Keyspace()
HOST_MARK = re.compile(r"cronsun\.[a-z_.]+")     # tracereduce.py's
T0 = 1_753_970_000
STEP_LEAVES = {
    True: {"drain", "lead", "reconcile", "flush", "cursor", "dispatch",
           "grant", "stall"},
    False: {"drain", "lead", "reconcile", "flush", "cursor", "plan",
            "dispatch", "grant", "build", "publish"},
}


def seed(store, n_jobs=6, n_nodes=3, kinds=(0, 2, 1), prefix="sj"):
    for i in range(n_nodes):
        store.put(KS.node_key(f"sn{i}"), "host:1")
    for i in range(n_jobs):
        job = Job(id=f"{prefix}{i:02d}", name=f"s{i}", group="g",
                  command="true", kind=kinds[i % len(kinds)],
                  rules=[JobRule(id="r", timer="* * * * * *",
                                 nids=[f"sn{i % n_nodes}"])])
        job.check()
        store.put(KS.job_key("g", job.id), job.to_json())


def service(store, pipelined=True, **kw):
    kw.setdefault("job_capacity", 64)
    kw.setdefault("node_capacity", 8)
    return SchedulerService(store, window_s=1, node_id="span-sched",
                            pipelined=None if pipelined else False, **kw)


def run_steps(svc, n, t=T0):
    for _ in range(n):
        svc.step(now=t)
        t = svc._next_epoch or t + 1
    return t


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_span_adds_to_the_ring_of_its_name_and_annotates():
    seen = []

    class Note:
        def __init__(self, name, **ids):
            self.name, self.ids = name, ids

        def __enter__(self):
            seen.append(("in", self.name, self.ids))

        def __exit__(self, *exc):
            seen.append(("out", self.name))

    sp = Spans("step", Note)
    with sp.span("flush", n=7) as s:
        time.sleep(0.002)
    assert s.ms >= 2.0
    assert sp.ring("flush").percentile(0.5) == s.ms
    assert seen == [("in", "cronsun.step.flush", {"n": 7}),
                    ("out", "cronsun.step.flush")]
    # ring= renames the ring, into= collects (summed) instead
    into = {}
    with sp.span("orders", ring="build", into=into):
        pass
    with sp.span("orders", ring="build", into=into):
        pass
    assert set(into) == {"build"} and len(sp.ring("build")) == 0
    sp.commit(into)
    assert len(sp.ring("build")) == 1
    # since=: measured from where the work began on another thread
    with sp.span("window", since=time.perf_counter() - 1.0) as s:
        pass
    assert s.ms >= 1000.0


@pytest.mark.parametrize("module", [
    "cronsun_tpu.node.agent", "cronsun_tpu.bin.node",
    "cronsun_tpu.bin.store", "cronsun_tpu.bin.logd", "cronsun_tpu.bin.web"])
def test_a_holder_without_a_factory_imports_no_jax(module):
    """Agents, store, logd and web import ``metrics``: only the
    scheduler maps JAX (``benchmarks/run.py`` checks ``jax_mapped_in``)."""
    code = (f"import sys, {module}\n"
            "from cronsun_tpu.metrics import Spans\n"
            "s = Spans('agent')\n"
            "with s.span('poll', n=1) as sp: pass\n"
            "assert len(s.ring('poll')) == 1 and sp.ms >= 0\n"
            "assert 'jax' not in sys.modules, 'jax imported'\n")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]


# ---------------------------------------------------------------------------
# the step-thread spans tile step()
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["pipelined", "serial"])
def test_step_spans_tile_the_step(pipelined):
    store = MemStore()
    seed(store)
    svc = service(store, pipelined=pipelined)
    assert svc.pipelined is pipelined
    t = run_steps(svc, 2)           # the compiles
    svc.reset_latency_stats()
    glue = []
    for _ in range(9):
        svc.step(now=t)
        t = svc._next_epoch
        spans = dict(svc._step_spans)
        total = spans.pop("total")
        assert set(spans) == STEP_LEAVES[pipelined]
        glue.append(total - sum(spans.values()))
    assert min(glue) > -0.01, glue
    assert sorted(glue)[len(glue) // 2] < 1.0, \
        f"step() has {glue} ms no span names"
    snap = svc.metrics_snapshot()
    for name in STEP_LEAVES[pipelined] | {"total", "snapshot"}:
        assert f"step_span_{name}_p50_ms" in snap, name
        assert len(svc._span_hist[name]) == 9, name
    if pipelined:
        # the dispatch thread's and the build worker's stages land in
        # the same rings
        svc._pending_plan[1].result()
        for name in ("gather", "build", "submit", "plan"):
            assert len(svc._span_hist[name]) >= 9, name
    assert snap["sched_step_p50_ms"] == snap["step_span_total_p50_ms"]
    assert 0 < snap["sched_step_cpu_p50_ms"] <= snap["sched_step_p99_ms"]
    svc.stop()
    store.close()


@pytest.mark.parametrize("leading", [True, False],
                         ids=["leader", "standby"])
def test_duty_counts_a_leaders_steps_and_waits_only(leading):
    store = MemStore()
    seed(store)
    if not leading:
        assert store.put_if_absent(KS.leader, "someone-else",
                                   lease=store.grant(60))
    svc = service(store)
    run_steps(svc, 3)
    for _ in range(3):
        assert svc._wait(0.02) is False
    snap = svc.metrics_snapshot()
    if leading:
        assert len(svc._span_hist["wait"]) == 3
        assert svc._span_hist["wait"].percentile(0.5) >= 20.0
        assert 0.0 < snap["step_duty_pct"] < 100.0
        busy = svc._span_hist["total"].sum()
        wait = svc._span_hist["wait"].sum()
        assert snap["step_duty_pct"] == round(100 * busy / (busy + wait), 3)
    else:
        # a standby's spans never reach the rings the gauges read
        assert not any(len(r) for r in svc._span_hist.values())
        assert snap["step_duty_pct"] == 0.0
        assert snap["steps_total"] == 0
    svc._stop.set()
    assert svc._wait(5.0) is True       # stopping cuts the wait short
    svc.stop()
    store.close()


# ---------------------------------------------------------------------------
# names, and the shared window id on the profiler's timeline
# ---------------------------------------------------------------------------

class RecordingNote:
    """Stands in for ``jax.profiler.TraceAnnotation``."""
    names = collections.Counter()
    depth = collections.Counter()       # thread -> open spans
    deepest = 0

    def __init__(self, name, **ids):
        self.name = name
        assert all(isinstance(v, int) for v in ids.values()), ids

    def __enter__(self):
        cls = RecordingNote
        cls.names[self.name] += 1
        cls.depth[threading.get_ident()] += 1
        cls.deepest = max(cls.deepest, cls.depth[threading.get_ident()])

    def __exit__(self, *exc):
        RecordingNote.depth[threading.get_ident()] -= 1


@pytest.mark.parametrize("pipelined", [True, False],
                         ids=["pipelined", "serial"])
def test_every_span_name_is_a_host_mark_and_a_leaf(pipelined, monkeypatch):
    RecordingNote.names.clear()
    RecordingNote.depth.clear()
    RecordingNote.deepest = 0
    monkeypatch.setattr(service_mod, "TraceAnnotation", RecordingNote)
    store = MemStore()
    seed(store)
    svc = service(store, pipelined=pipelined)
    run_steps(svc, 3)
    svc._wait(0.01)
    svc._start_warm()
    while svc._warm_thread is not None:
        time.sleep(0.05)
    svc.stop()
    store.close()
    names = set(RecordingNote.names)
    for name in names:
        assert HOST_MARK.fullmatch(name), name
    want = {f"cronsun.step.{n}" for n in STEP_LEAVES[pipelined]
            | {"snapshot", "wait"}}
    want |= {"cronsun.publish.window", "cronsun.warm.compile"}
    if pipelined:
        want |= {"cronsun.build.orders", "cronsun.build.submit"}
    assert names == want
    assert RecordingNote.deepest == 1, "a span enclosed another"


def test_a_windows_spans_share_w_on_the_profilers_timeline(tmp_path):
    """The real annotation, a real profiler session: names come out
    clean, ``n`` and ``w`` land as event stats, and the window planned
    from second w carries that w from the dispatch thread through the
    build worker to the publisher."""
    import jax
    from jax.profiler import ProfileData
    store = MemStore()
    seed(store)
    svc = service(store, sync_publish=False)
    t = run_steps(svc, 2)
    svc._builder.flush()
    svc.publisher.flush()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run_steps(svc, 3, t)
        svc._wait(0.6)          # three slices: 0.25, 0.25, 0.1
        svc._builder.flush()
        svc.publisher.flush()
    finally:
        jax.profiler.stop_trace()
    svc.stop()
    store.close()
    found = list(tmp_path.rglob("*.xplane.pb"))
    assert found, "the session left no trace"
    by_name = collections.defaultdict(list)
    for plane in ProfileData.from_file(str(found[0])).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("cronsun."):
                    assert HOST_MARK.fullmatch(ev.name), ev.name
                    by_name[ev.name].append(
                        {k: v for k, v in ev.stats if k in ("n", "w")})
    for name in ("cronsun.step.drain", "cronsun.step.flush",
                 "cronsun.step.grant", "cronsun.step.stall"):
        assert len(by_name[name]) == 3, (name, by_name[name])
        assert all("n" in ids for ids in by_name[name])
    assert len(by_name["cronsun.step.wait"]) == 3
    # step k planned the window from second t + k (window_s 1) and
    # dispatched the one after it
    windows = {t, t + 1, t + 2}
    for name in ("cronsun.plan.gather", "cronsun.build.orders",
                 "cronsun.build.submit", "cronsun.publish.window"):
        assert {int(ids["w"]) for ids in by_name[name]} == windows, name
    assert windows - {t} <= {int(ids["w"])
                             for ids in by_name["cronsun.plan.dispatch"]}


# ---------------------------------------------------------------------------
# cold load by phase, warm-up, first publish
# ---------------------------------------------------------------------------

COLD = ("startup", "planner", "lists", "jobs", "device")


def test_process_age_reads_the_os_clock():
    age = process_age_s()
    assert age is not None and 0.0 < age < 24 * 3600
    time.sleep(0.05)
    assert 0.03 < process_age_s() - age < 1.0
    clock = PhaseClock.from_process_start()
    assert time.monotonic() - clock.t0 >= age


@pytest.mark.parametrize("restore", [False, True],
                         ids=["cold-load", "checkpoint-restore"])
def test_cold_phases_tile_the_start_up(restore, tmp_path):
    store = MemStore()
    seed(store, n_jobs=40, n_nodes=6)
    kw = {"checkpoint_dir": str(tmp_path)} if restore else {}
    if restore:
        first = service(store, **kw)
        first.checkpoint_save()
        first.stop()
    cold = PhaseClock(age_s=0.2)        # "the OS started it 0.2 s ago"
    cold.mark("startup")
    svc = service(store, cold=cold, **kw)
    cold.mark("ready")
    snap = svc.metrics_snapshot()
    named = sum(snap[f"cold_{k}_s"] for k in COLD)
    assert 0.2 <= snap["cold_startup_s"] < 0.25
    assert snap["cold_planner_s"] > 0
    if restore:
        assert snap["checkpoint_restored"] == 1
        assert snap["cold_jobs_s"] == 0.0 and snap["cold_lists_s"] < 0.05
        named += cold.seconds["restore"]
    else:
        assert min(snap[f"cold_{k}_s"] for k in COLD) > 0
    assert abs(snap["cold_total_s"] - named) < 0.05, snap
    assert abs(snap["cold_total_s"] - (time.monotonic() - cold.t0)) < 0.5
    svc.stop()
    store.close()


def test_warm_and_first_publish_are_stamped_where_the_work_is():
    store = MemStore()
    seed(store)
    svc = service(store, sync_publish=False)
    snap = svc.metrics_snapshot()
    assert snap["warm_s"] == 0.0 and snap["first_publish_s"] == 0.0
    t_lead = time.monotonic()
    run_steps(svc, 1)
    svc._builder.flush()
    svc.publisher.flush()       # ... until the HWM put is acknowledged
    took = time.monotonic() - t_lead
    first = svc.metrics_snapshot()["first_publish_s"]
    assert 0.0 < first <= took + 0.001      # the gauge is rounded
    assert svc._first_pub_wait is None
    run_steps(svc, 2, svc._next_epoch)
    svc.publisher.flush()
    assert svc.metrics_snapshot()["first_publish_s"] == first, \
        "a later window moved the first publish"
    svc._start_warm()
    while svc._warm_thread is not None:
        time.sleep(0.05)
    assert svc.metrics_snapshot()["warm_s"] > 0.0
    svc.stop()
    store.close()


# ---------------------------------------------------------------------------
# compilations on the served path
# ---------------------------------------------------------------------------

def test_compiles_leading_counts_the_served_paths_compiles():
    store = MemStore()
    seed(store, n_jobs=5, n_nodes=3)
    # shapes no other test of the process uses (N = 96)
    svc = service(store, job_capacity=512, node_capacity=96)
    assert svc.stats["compiles_total"] > 0, "the cold load compiled"
    assert svc.stats["compile_s_total"] > 0.0
    assert svc.stats["compiles_leading_total"] == 0, "not leading yet"
    t = run_steps(svc, 3)
    svc._start_warm()
    while svc._warm_thread is not None:
        time.sleep(0.05)
    warmed = dict(svc.stats)
    assert warmed["compiles_leading_total"] > 0, \
        "the first window's plan compiled while leading"
    t = run_steps(svc, 10, t)
    assert svc.stats["compiles_leading_total"] == \
        warmed["compiles_leading_total"], "a warmed step compiled"
    # 40 new jobs: scatters padded to 64 rows, a size never seen
    seed(store, n_jobs=40, n_nodes=3, prefix="late")
    run_steps(svc, 1, t)
    snap = svc.metrics_snapshot()
    assert snap["compiles_leading_total"] > \
        warmed["compiles_leading_total"]
    assert snap["compiles_total"] >= snap["compiles_leading_total"]
    assert snap["cache_loads_total"] <= snap["compiles_total"]
    svc.stop()
    store.close()


def test_one_compile_listener_a_process():
    store = MemStore()
    a, b = service(store), service(store)
    assert service_mod._compile_listening
    assert {a, b} <= set(service_mod._compile_sinks)
    before = (a.stats["compiles_total"], b.stats["compiles_total"])
    service_mod._on_compile_event(service_mod._COMPILE_EVENT, 0.5)
    service_mod._on_compile_event(service_mod._CACHE_LOAD_EVENT, 0.1)
    service_mod._on_compile_event("/jax/some/other/event", 9.0)
    for svc, n in zip((a, b), before):
        assert svc.stats["compiles_total"] == n + 1
        assert svc.stats["cache_loads_total"] >= 1
    for svc in (a, b):
        svc.stop()
    store.close()


# ---------------------------------------------------------------------------
# what the order build drops silently, counted one per fire — and what it
# does not drop: an Alone fire, whatever its lifetime lock says
# ---------------------------------------------------------------------------

def build_fixture():
    """Six jobs on three nodes: Common, Interval, Alone in turn, each
    pinned to one node.  Returns (store, service, rows by job id)."""
    store = MemStore()
    seed(store)
    svc = service(store)
    rows = {jid: row for row, (_g, jid, _r) in svc.rows.by_row.items()}
    return store, svc, rows


def plan_of(svc, fires):
    """A TickPlan firing [(row, node id)], as the device would place
    them."""
    return TickPlan(
        epoch_s=T0, overflow=0,
        fired=np.asarray([r for r, _n in fires], np.int32),
        assigned=np.asarray([svc.universe.index[n] for _r, n in fires],
                            np.int32))


def late_ring(svc, plan):
    """The plan's fires as spill-ring arrivals for a second that has
    already shipped: the late path of ``_smear_begin``."""
    svc._smear_ring[T0 - 5] = {T0 - 9: [
        np.asarray(plan.fired, np.int64),
        np.asarray(plan.assigned, np.int64), None]}
    svc._smear_ring_n = int(plan.fired.size)


def build(svc, plan, site):
    seconds, acct = [], []
    if site == "native":
        svc._build_plan_orders_native(plan, seconds, acct)
    elif site == "ref":
        svc._build_plan_orders_ref(plan, seconds, acct)
    else:
        late_ring(svc, plan)
        svc._smear_begin(T0, seconds, acct)
    return [k + " " + v for _s, orders in seconds for k, v in orders]


@pytest.mark.parametrize("site", ["native", "ref", "late"])
def test_alone_fire_is_ordered_whatever_its_lock_says(site):
    """The scheduler judges no KindAlone lock: with sj02's lifetime lock
    live in the store when the window is built, every due fire of it is
    ordered to its node like an Interval fire (the node tries the lock
    at the fire's second), and the two builders agree byte for byte."""
    store, svc, rows = build_fixture()
    # sj02 and sj05 are Alone; sj02's previous run is live
    store.put(KS.alone_lock_key("sj02"), "held")
    svc.drain_watches()
    plan = plan_of(svc, [(rows["sj02"], "sn2"), (rows["sj05"], "sn2"),
                         (rows["sj01"], "sn1"), (rows["sj02"], "sn2")])
    orders = build(svc, plan, site)
    assert sum(o.count("sj02") for o in orders) == 2, "both fires"
    assert all("/sn2/" in o for o in orders if "sj02" in o or "sj05" in o)
    assert sum(o.count("sj05") for o in orders) == 1
    assert build(svc, plan, "native") == build(svc, plan, "ref")
    assert svc.stats["fires_node_gone_total"] == 0
    assert svc.metrics_snapshot()["alone_left_out_total"] == 0
    # the lock is let go: the same orders
    store.delete(KS.alone_lock_key("sj02"))
    svc.drain_watches()
    assert build(svc, plan, site) == orders
    svc.stop()
    store.close()


@pytest.mark.parametrize("site", ["native", "ref", "late"])
def test_fires_node_gone_counts_each_fire_placed_on_a_node_that_left(site):
    store, svc, rows = build_fixture()
    # planned while sn1 was up ...
    plan = plan_of(svc, [(rows["sj01"], "sn1"), (rows["sj04"], "sn1"),
                         (rows["sj02"], "sn2"), (rows["sj05"], "sn2")])
    # ... an unplaced fire (column -1) is not a node that left
    plan.assigned[2] = -1
    build(svc, plan, site)
    assert svc.stats["fires_node_gone_total"] == 0
    # ... built after it left the fleet
    store.delete(KS.node_key("sn1"))
    svc.drain_watches()
    orders = build(svc, plan, site)
    assert svc.stats["fires_node_gone_total"] == 2, "one per dropped fire"
    assert not any("/sn1/" in o for o in orders)
    assert any("/sn2/" in o and "sj05" in o for o in orders)
    assert svc.metrics_snapshot()["fires_node_gone_total"] == 2
    svc.stop()
    store.close()
