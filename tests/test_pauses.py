"""The scheduler's own pauses, named (``metrics.open_leaf``,
``metrics.gc_pauses``, ``SchedulerService._count_compile`` and the flush
by part): a collector pass is counted under the leaf open on its thread
and a full one is the one nested annotation; a served-path compile is
counted under its leaf and timed, and one inside the flush is logged
once with its part and padded rows; the flush's parts are timed only
where a leading step had work; the agent's burst account holds the
collector's ms; the snapshot keys nothing read are gone.
"""

import gc
import json
import logging
import sys
import threading
import time

import jax
import numpy as np
import pytest

from cronsun_tpu import log
from cronsun_tpu.metrics import Gains, Spans, gc_pauses, leaf_key, open_leaf
from cronsun_tpu.store import MemStore

from test_sched_spans import KS, run_steps, seed, service

# a table no other test of the process uses (J = 8,192 rows), so the
# flush's scatters meet padded sizes never compiled before
SHAPES = {"job_capacity": 8192, "node_capacity": 112}
REMOVED = ("publish_inflight", "publish_shard_lanes",
           "smear_merged_dups_total", "smear_max_second_arrivals",
           "dep_events_mirrored", "tenant_shed_fires_total")


@pytest.fixture
def collector_off():
    """No pass but the ones a test asks for, on any thread."""
    gc_pauses.install()
    was = gc.isenabled()
    gc.disable()
    yield
    if was:
        gc.enable()


@pytest.fixture
def lines():
    """The process log's messages."""
    got = []

    class Keep(logging.Handler):
        def emit(self, record):
            got.append(record.getMessage())

    old, lg = log._logger, logging.Logger("cronsun-test-pauses")
    lg.addHandler(Keep())
    log.set_logger(lg)
    yield got
    log.set_logger(old)


def gained(before: dict, after: dict, key: str):
    return after.get(key, 0) - before.get(key, 0)


# ---------------------------------------------------------------------------
# the open leaf
# ---------------------------------------------------------------------------

def test_the_open_leaf_is_none_outside_spans_and_per_thread():
    assert open_leaf() == "none"
    step, plan = Spans("step"), Spans("plan")     # plan: ring-only holder
    inside = threading.Event()
    leave = threading.Event()
    seen = {}

    def other():
        seen["before"] = open_leaf()
        with plan.span("gather"):
            seen["inside"] = open_leaf()
            inside.set()
            leave.wait(10)
        seen["after"] = open_leaf()

    with step.span("flush"):
        t = threading.Thread(target=other)
        t.start()
        assert inside.wait(10)
        assert open_leaf() == "step.flush"
        leave.set()
        t.join(10)
        assert open_leaf() == "step.flush"
    assert open_leaf() == "none"
    assert seen == {"before": "none", "inside": "plan.gather",
                    "after": "none"}
    assert leaf_key("plan.gather") == "gather" and leaf_key("none") == "none"


# ---------------------------------------------------------------------------
# the collector's passes
# ---------------------------------------------------------------------------

def test_a_full_pass_is_counted_under_its_leaf_and_a_young_one_is_not(
        collector_off):
    sp = Spans("step")
    before, young = gc_pauses.totals(), list(gc_pauses.passes)
    with sp.span("flush"):
        gc.collect()
    mid = gc_pauses.totals()
    assert gained(before, mid, "full_passes") == 1
    assert gained(before, mid, "full_ms_flush") > 0
    assert gained(before, mid, "full_ms") == pytest.approx(
        gained(before, mid, "full_ms_flush"))
    with sp.span("drain"):
        gc.collect(0)
        gc.collect(1)
    after = gc_pauses.totals()
    assert gained(mid, after, "full_passes") == 0
    assert gained(mid, after, "full_ms") == 0
    assert "full_ms_drain" not in after or \
        gained(mid, after, "full_ms_drain") == 0
    assert [n - m for n, m in zip(gc_pauses.passes, young)] == [1, 1, 1]
    assert gained(mid, after, "pause_ms") > 0
    assert gc_pauses.pause_ms() == pytest.approx(after["pause_ms"])


@pytest.mark.parametrize("recording", [True, False],
                         ids=["trace-on", "trace-off"])
def test_the_factory_sees_one_gc_full_per_full_pass(collector_off,
                                                    recording):
    seen = []
    depth = []

    class Note:
        def __init__(self, name, **ids):
            self.name, self.ids = name, ids

        def __enter__(self):
            depth.append(self.name)
            seen.append((self.name, self.ids))

        def __exit__(self, *exc):
            depth.remove(self.name)

        @staticmethod
        def is_enabled():
            return recording

    saved = gc_pauses.annotate
    gc_pauses.install(Note)
    try:
        sp = Spans("step", Note)
        with sp.span("flush"):
            gc.collect()
            gc.collect(0)
        gc.collect()
        gc.collect(1)
        gc.collect()
    finally:
        gc_pauses.annotate = saved
    fulls = [ids for name, ids in seen if name == "cronsun.gc.full"]
    assert len(fulls) == 3, seen
    assert [name for name, _ids in seen].count("cronsun.step.flush") == 1
    assert not depth, "an annotation was left open"
    if recording:
        assert all(ids["objects"] > 0 for ids in fulls)
    else:
        assert all(ids == {} for ids in fulls), "counted with no trace"


def test_no_pass_is_lost_with_more_threads_than_cores():
    """Eight threads making cyclic garbage, passes triggered on all of
    them and a 10 µs switch interval: the account counts exactly the
    passes the interpreter counts, generation by generation."""
    gc_pauses.install()
    old = sys.getswitchinterval()
    before = [s["collections"] for s in gc.get_stats()]
    mine = list(gc_pauses.passes)
    sys.setswitchinterval(1e-5)

    def churn():
        for _ in range(20_000):
            a, b = [], []
            a.append(b)
            b.append(a)

    try:
        threads = [threading.Thread(target=churn) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    after = [s["collections"] for s in gc.get_stats()]
    gained = [a - b for a, b in zip(after, before)]
    assert sum(gained) > 50, gained
    assert [n - m for n, m in zip(gc_pauses.passes, mine)] == gained


def test_gains_add_only_the_stretches_that_held():
    box = {"a": 0.0}
    g = Gains(lambda: dict(box))
    box["a"] = 5.0
    g.poll(False)
    box["a"] = 7.0
    box["b"] = 1
    g.poll(True)
    box["a"] = 10.0
    g.poll(False)
    assert g.total == {"a": 2.0, "b": 1}


# ---------------------------------------------------------------------------
# the scheduler: compiles and passes where they landed, the flush by part
# ---------------------------------------------------------------------------

def leaves_sum(snap: dict) -> int:
    return sum(v for k, v in snap.items()
               if k.startswith("compiles_leading_") and k.endswith("_total")
               and k != "compiles_leading_total")


def test_a_served_compile_is_placed_in_its_leaf_and_timed(lines):
    store = MemStore()
    seed(store, n_jobs=5, n_nodes=3)
    svc = service(store, **SHAPES)
    t = run_steps(svc, 2)
    assert svc.is_leader
    svc._pending_plan[1].result()       # the prefetched window's compile
    while svc._warm_thread is not None:     # the step started the warm-up
        time.sleep(0.05)
    snap = svc.metrics_snapshot()
    assert snap["compiles_leading_flush_total"] == 0
    assert leaves_sum(snap) == snap["compiles_leading_total"] > 0, \
        "the first window's plan compiled while leading"

    # the warm thread's compiles add to neither
    x, y = np.ones((7, 13), np.float32), np.ones((11, 17), np.float32)

    def warm():
        jax.jit(lambda a: a * 3 + 1)(x).block_until_ready()
    svc._warm_thread = threading.Thread(target=warm)
    svc._warm_thread.start()
    svc._warm_thread.join(60)
    svc._warm_thread = None
    warmed = svc.metrics_snapshot()
    for key in ("compiles_leading_total", "compile_leading_s_total",
                "compiles_leading_flush_total"):
        assert warmed[key] == snap[key], key
    assert warmed["compiles_total"] > snap["compiles_total"]

    # a never-seen shape jitted inside the leader's flush span
    with svc._spans.span("flush"):
        jax.jit(lambda a: a * 5 - 2)(y).block_until_ready()
    one = svc.metrics_snapshot()
    assert one["compiles_leading_flush_total"] == 1
    assert one["compiles_leading_total"] == \
        warmed["compiles_leading_total"] + 1
    assert one["compile_leading_s_total"] > warmed["compile_leading_s_total"]
    assert leaves_sum(one) == one["compiles_leading_total"]

    # 40 new jobs: the table's scatters padded to 64 rows, a size the
    # leader never flushed — logged once a (part, padded rows)
    seed(store, n_jobs=40, n_nodes=3, prefix="late")
    t = run_steps(svc, 1, t)
    grown = svc.metrics_snapshot()
    assert grown["compiles_leading_flush_total"] > 1
    assert leaves_sum(grown) == grown["compiles_leading_total"]
    assert grown["compile_s_total"] >= grown["compile_leading_s_total"]
    said = [ln for ln in lines if ln.startswith("flush compiled ")]
    assert said and len(said) == len(set(
        ln.rsplit(" (", 1)[0] for ln in said)), said
    parts = {ln.split()[2] for ln in said}
    assert parts <= set(svc.FLUSH_PARTS) and "table" in parts
    assert any(" table at 64 rows (" in ln for ln in said), said
    svc.stop()
    store.close()


@pytest.mark.parametrize("leading", [True, False],
                         ids=["leader", "standby"])
def test_flush_parts_ring_only_a_leading_steps_parts_that_had_work(leading):
    store = MemStore()
    seed(store)
    if not leading:
        assert store.put_if_absent(KS.leader, "someone-else",
                                   lease=store.grant(60))
    svc = service(store)
    t = run_steps(svc, 2)
    parts = svc.FLUSH_PARTS
    assert all(len(svc._span_hist["flush_" + p]) == 0 for p in parts), \
        "the cold load's flush is nobody's step"
    seed(store, n_jobs=9, n_nodes=3, prefix="more")
    run_steps(svc, 2, t)
    snap = svc.metrics_snapshot()
    for p in parts:
        assert f"step_span_flush_{p}_p50_ms" in snap
        assert f"step_span_flush_{p}_p99_ms" in snap
        assert f"flush_rows_{p}_total" in snap
    rings = {p: len(svc._span_hist["flush_" + p]) for p in parts}
    if leading:
        # one step had the new jobs to write, the next nothing
        assert rings == {"tenant": 0, "table": 1, "elig": 1, "meta": 1,
                         "deps": 0}
        for p in ("table", "elig", "meta"):
            assert snap[f"flush_rows_{p}_total"] == 9
            assert snap[f"step_span_flush_{p}_p50_ms"] > 0
        assert snap["flush_rows_deps_total"] == 0
        assert snap["step_span_flush_deps_p50_ms"] == 0.0
        assert not any(k.startswith("flush_") for k in svc._step_spans), \
            "a part is not a leaf"
    else:
        assert set(rings.values()) == {0}
        assert all(snap[f"flush_rows_{p}_total"] == 0 for p in parts)
    svc.stop()
    store.close()


@pytest.mark.parametrize("leading", [True, False],
                         ids=["leader", "standby"])
def test_collector_passes_count_while_leading(collector_off, leading):
    store = MemStore()
    seed(store)
    if not leading:
        assert store.put_if_absent(KS.leader, "someone-else",
                                   lease=store.grant(60))
    svc = service(store)
    t = run_steps(svc, 2)
    gc.collect()                  # the loop's glue: no leaf open
    run_steps(svc, 1, t)
    snap = svc.metrics_snapshot()
    if leading:
        assert snap["gc_full_passes_leading_total"] == 1
        assert snap["gc_full_ms_leading_none_total"] > 0
        assert snap["gc_full_ms_leading_total"] == pytest.approx(
            sum(v for k, v in snap.items()
                if k.startswith("gc_full_ms_leading_")
                and k != "gc_full_ms_leading_total"), abs=0.01)
        assert snap["gc_pause_ms_leading_total"] >= \
            snap["gc_full_ms_leading_total"] > 0
    else:
        assert snap["gc_full_passes_leading_total"] == 0
        assert snap["gc_pause_ms_leading_total"] == 0.0
        assert not [k for k in snap if k.startswith("gc_full_ms_leading_")
                    and k != "gc_full_ms_leading_total"]
    svc.stop()
    store.close()


def test_the_unread_snapshot_keys_are_gone():
    store = MemStore()
    seed(store)
    svc = service(store)
    run_steps(svc, 2)
    snap = svc.metrics_snapshot()
    json.dumps(snap)
    assert not set(REMOVED) & set(snap)
    # what stays: the smear counters behind smear_snapshot()
    smear = svc.smear_snapshot()
    assert {"merged_dups_total", "max_second_arrivals"} <= set(smear)
    for key in ("publish_max_second_keys", "published_total",
                "checkpoint_saves_total", "compiles_leading_total"):
        assert key in snap, key
    svc.stop()
    store.close()


# ---------------------------------------------------------------------------
# the agent's account of a burst second
# ---------------------------------------------------------------------------

def test_an_agent_burst_holds_the_collectors_ms(collector_off):
    from test_agent_spans import T0, Rig
    rig = Rig()
    try:
        real = rig.executor.run_once
        ran = []

        def run_once(command, user="", timeout=0, env=None):
            if not ran:
                ran.append(1)
                gc.collect()          # a full pass inside the burst
            return real(command, user, timeout, env)

        rig.executor.run_once = run_once
        before = rig.agent.metrics_snapshot()
        rig.run_second("common", 32, T0 + 5)
        rec = rig.agent._herds[0][1]
        assert rec["gc_ms"] > 0
        snap = rig.agent.metrics_snapshot()
        assert snap["herd_gc_ms"] == rec["gc_ms"]
        assert snap["gc_full_ms_total"] > before["gc_full_ms_total"]
        assert snap["gc_pause_ms_total"] >= snap["gc_full_ms_total"]
        assert snap["gc_pause_ms_total"] - before["gc_pause_ms_total"] \
            >= rec["gc_ms"] - 0.002
    finally:
        rig.close()

