"""One execution's life on the agent, stage by stage, and the account of
a burst second (``node/agent.py`` STAGES, ``_Second``, the GIL probe):
the stages tile the execution on both ways into the pool, a second of
32 closes exactly one burst record and a second of 4 none, the probe
thread lives only while a burst is open, the snapshot carries every
field ``PERF.md`` names, and no commit is lost with more workers than
cores.
"""

import json
import logging
import os
import re
import statistics
import sys
import threading
import time

import pytest

from cronsun_tpu import log
from cronsun_tpu.core import Job, JobRule, Keyspace, KIND_COMMON
from cronsun_tpu.logsink import JobLogStore
from cronsun_tpu.node import agent as agent_mod
from cronsun_tpu.node.agent import HERD_MIN, STAGES, NodeAgent
from cronsun_tpu.node.executor import ExecResult, Executor
from cronsun_tpu.store import MemStore

KS = Keyspace()
T0 = 1_753_970_000
PATHS = ["common", "bundle"]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class InstantExecutor(Executor):
    """No fork, no exec: the stamps ``run_once`` takes, around nothing —
    or, while ``hold`` is clear, around a child that does not end."""

    def __init__(self, clock):
        super().__init__(clock=clock)
        self.hold = threading.Event()
        self.hold.set()

    def run_once(self, command, user="", timeout=0, env=None):
        begin, t_begin = self.clock(), time.perf_counter()
        t_spawned = time.perf_counter()
        assert self.hold.wait(30)
        return ExecResult(True, "", begin, self.clock(),
                          spawn_s=t_spawned - t_begin, t_begin=t_begin,
                          t_spawned=t_spawned, t_end=time.perf_counter())


class Rig:
    """An agent on a clock the test moves, an in-process store and sink."""

    def __init__(self):
        self.store, self.sink = MemStore(), JobLogStore()
        self.now = [float(T0)]
        clock = lambda: self.now[0]
        self.executor = InstantExecutor(clock)
        self.agent = NodeAgent(self.store, self.sink, node_id="n0",
                               executor=self.executor, clock=clock)
        self.agent.register()
        self._seq = self.ordered = 0

    def order(self, path, n, epoch):
        """``n`` executions due at ``epoch``: Common broadcasts, or the
        members of one (node, second) bundle."""
        ids = []
        self.ordered += n
        for _ in range(n):
            self._seq += 1
            job = Job(id=f"sp{self._seq}", name=f"sp{self._seq}", group="g",
                      command="true",
                      kind=KIND_COMMON if path == "common" else 2,
                      rules=[JobRule(id="r", timer="* * * * * *",
                                     nids=["n0"])])
            self.store.put(KS.job_key("g", job.id), job.to_json())
            ids.append(job.id)
        if path == "common":
            for jid in ids:
                self.store.put(KS.dispatch_all_key(epoch, "g", jid), "")
        else:
            self.store.put(KS.dispatch_bundle_key("n0", epoch),
                           json.dumps([f"g/{jid}" for jid in ids]))
        self.agent.poll()
        return ids

    def run_second(self, path, n, epoch):
        """Stage ``n`` executions ahead of ``epoch``, let the second
        arrive, wait for them all."""
        self.order(path, n, epoch)
        self.now[0] = epoch + 0.25
        self.wait_recorded()

    def wait_recorded(self):
        """Every execution ordered so far recorded, every account closed."""
        deadline = time.monotonic() + 30
        while self.agent.stats["execs_total"] < self.ordered \
                or self.agent._seconds:
            assert time.monotonic() < deadline, "executions did not end"
            time.sleep(0.01)
        self.agent.join_running()

    def close(self):
        self.executor.hold.set()
        self.agent.stop()
        self.store.close()


@pytest.fixture
def rig():
    r = Rig()
    yield r
    r.close()


@pytest.fixture
def herd_lines():
    """The agent's log lines, one list entry each."""
    lines = []

    class Keep(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    old, lg = log._logger, logging.Logger("cronsun-test-spans")
    lg.addHandler(Keep())
    log.set_logger(lg)
    yield lines
    log.set_logger(old)


def probe_threads():
    return [t for t in threading.enumerate()
            if t.name == "gilprobe-n0" and t.is_alive()]


def ring(agent, name):
    return agent._spans.rings[name].values()


# ---------------------------------------------------------------------------
# the stages tile one execution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", PATHS)
def test_stages_tile_the_execution(rig, path):
    rig.run_second(path, 32, T0 + 5)
    whole = ring(rig.agent, "whole")
    assert len(whole) == 32
    leaves = [ring(rig.agent, name) for name in STAGES]
    assert all(len(v) == 32 for v in leaves), "one sample a stage"
    # one commit an execution: sample i of every ring is one execution
    glue = [w - sum(v[i] for v in leaves) for i, w in enumerate(whole)]
    assert abs(statistics.median(glue)) < 1.0, glue
    assert all(v >= 0.0 for leaf in leaves for v in leaf)
    # released at most 20 ms early, at most one 100 ms scan late (the
    # clock here jumped 0.25 s past the second)
    assert all(-20.0 <= v <= 250.0 for v in ring(rig.agent, "enqueue_late"))
    assert len(ring(rig.agent, "dep_put")) == 32
    assert len(ring(rig.agent, "bundle_claim")) == (path == "bundle")
    assert len(ring(rig.agent, "bundle_prefetch")) == (path == "bundle")
    # rank by rank the herd waits on the pool's queue, not in the stage
    assert sum(ring(rig.agent, "queue")) > 0


def test_the_real_executor_stamps_where_the_run_changes_hands():
    res = Executor().run_once("true")
    assert res.success
    assert 0 < res.t_begin <= res.t_spawned <= res.t_end
    assert res.spawn_s <= res.t_spawned - res.t_begin
    res = Executor().run_once("'")        # never launched: no stamps
    assert not res.success and res.t_begin == res.t_spawned == 0.0
    res = Executor().run_job("j", "false", retry=1)
    assert res.retries_used == 1 and res.t_begin <= res.t_spawned <= res.t_end


# ---------------------------------------------------------------------------
# the account of a burst second
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("path", PATHS)
def test_a_second_of_32_closes_one_burst_record(rig, herd_lines, path):
    epoch = T0 + 5
    rig.run_second(path, 32, epoch)
    herds = [json.loads(ln[5:]) for ln in herd_lines
             if ln.startswith("herd ")]
    assert len(herds) == 1 == len(rig.agent._herds)
    rec = herds[0]
    assert rec == rig.agent._herds[0][1]
    assert rec["sec"] == epoch and rec["n"] == 32
    for name in STAGES + ("whole", "dep_put"):
        assert rec[f"sum_{name}_ms"] > 0, name
    assert ("sum_bundle_claim_ms" in rec) == (path == "bundle")
    assert rec["lag_first_s"] == pytest.approx(0.25)
    assert rec["lag_last_s"] == pytest.approx(0.25)
    # an execution's lag is enqueue_late + queue + prelaunch (the clock
    # here stands at 0.25 s past the second; the two durations are real)
    lag_ms = sum(rec[f"sum_{name}_ms"] for name in
                 ("enqueue_late", "queue", "prelaunch")) / rec["n"]
    assert 250.0 <= lag_ms < 300.0
    assert rec["drain_s"] > 0 and rec["cpu_self_s"] >= 0
    assert rec["cpu_share"] >= 0 and rec["children_cpu_s"] == 0
    assert 1 <= rec["pool_busy_max"] <= rig.agent.max_inflight
    assert rec["gil_probe_p50_ms"] <= rec["gil_probe_p99_ms"] \
        <= rec["gil_probe_max_ms"]
    assert rig.agent._probes_armed == 0 and not rig.agent._seconds


@pytest.mark.parametrize("path", PATHS)
def test_a_second_of_4_closes_none(rig, herd_lines, path):
    rig.run_second(path, 4, T0 + 5)
    assert rig.agent.stats["execs_total"] == 4
    assert not rig.agent._herds and not rig.agent._seconds
    assert not [ln for ln in herd_lines if ln.startswith("herd ")]
    assert rig.agent._probe_thread is None and not probe_threads()
    assert len(ring(rig.agent, "whole")) == 4     # the rings still fill
    assert "herd_n" not in rig.agent.metrics_snapshot()


@pytest.mark.parametrize("path", PATHS)
def test_the_probe_lives_only_while_a_burst_is_open(rig, path):
    assert not probe_threads()
    rig.executor.hold.clear()           # children that do not end
    rig.order(path, 32, T0 + 5)
    assert not probe_threads(), "staged, not due: no burst yet"
    rig.now[0] = T0 + 5.25
    deadline = time.monotonic() + 10
    while rig.agent._pool is None or rig.agent._pool.busy < 32:
        assert time.monotonic() < deadline
        time.sleep(0.01)
    assert len(probe_threads()) == 1
    rig.executor.hold.set()
    rig.wait_recorded()
    deadline = time.monotonic() + 1.0
    while probe_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not probe_threads(), "the probe outlived its burst by 1 s"
    # it sampled while the burst was open (its p50 reads the samples
    # between the first and the last begin: none, the begins being
    # instant here)
    assert rig.agent._herds[0][1]["gil_probe_over_ms"] > 0
    assert rig.agent._herds[0][1]["pool_busy_max"] >= 32


def test_a_burst_that_stays_open_does_not_keep_the_probe(rig, monkeypatch):
    """A herd second that holds a long job: the probe gives up after
    GIL_PROBE_MAX_S; the record still closes when the job ends."""
    monkeypatch.setattr(agent_mod, "GIL_PROBE_MAX_S", 0.2)
    rig.executor.hold.clear()
    rig.order("common", HERD_MIN, T0 + 5)
    rig.now[0] = T0 + 5.25
    deadline = time.monotonic() + 10
    while not probe_threads():
        assert time.monotonic() < deadline
        time.sleep(0.01)
    while probe_threads():
        assert time.monotonic() < deadline, "the probe did not give up"
        time.sleep(0.01)
    assert rig.agent._probes_armed == 1
    rig.executor.hold.set()
    rig.wait_recorded()
    assert rig.agent._probes_armed == 0
    assert rig.agent._herds[0][1]["n"] == HERD_MIN


def test_a_run_now_execution_opens_no_account(rig):
    job = Job(id="once1", name="once1", group="g", command="true",
              kind=KIND_COMMON,
              rules=[JobRule(id="r", timer="* * * * * *", nids=["n0"])])
    rig.store.put(KS.job_key("g", job.id), job.to_json())
    rig.store.put(KS.once_key("g", job.id), "n0")
    rig.ordered += 1
    rig.agent.poll()
    rig.wait_recorded()
    assert rig.agent.stats["execs_total"] == 1
    assert not rig.agent._seconds and not ring(rig.agent, "whole")
    assert rig.agent._pool is None


# ---------------------------------------------------------------------------
# the snapshot
# ---------------------------------------------------------------------------

def perf_md_fields():
    with open(os.path.join(REPO, "PERF.md")) as f:
        text = f.read()
    return set(re.findall(
        r"`((?:exec_span_[a-z_]+_p(?:50|99)_ms)|(?:herd_[a-z0-9_]+)"
        r"|avg_time_writebacks_total|pool_busy_max|pool_queue_max"
        r"|stage_scan_enqueued_max)`", text))


@pytest.mark.parametrize("path", PATHS)
def test_the_snapshot_carries_every_field_perf_md_names(rig, path):
    named = perf_md_fields()
    assert {"herd_drain_s", "herd_cpu_share", "herd_gil_probe_p50_ms",
            "exec_span_queue_p50_ms"} <= named, "PERF.md §3 / §7 (a)"
    rig.run_second(path, 32, T0 + 5)
    snap = rig.agent.metrics_snapshot()
    json.dumps(snap)
    for name in STAGES + ("whole", "enqueue_late", "dep_put"):
        assert f"exec_span_{name}_p50_ms" in snap
        assert snap[f"exec_span_{name}_p99_ms"] >= \
            snap[f"exec_span_{name}_p50_ms"]
    absent = {n for n in named if n not in snap}
    if path == "common":
        absent = {n for n in absent if "bundle_" not in n}
    if not os.path.exists("/proc/pressure/cpu"):
        absent.discard("herd_host_cpu_stall_share")
    if not os.path.exists("/proc/self/schedstat"):
        absent.discard("herd_gil_probe_runq_ms")
    assert not absent
    assert snap["herd_n"] == 32 and snap["herd_sec"] == T0 + 5
    assert snap["avg_time_writebacks_total"] == 0    # instant runs
    assert snap["stage_scan_enqueued_max"] == (32 if path == "common"
                                               else 1)
    assert snap["pool_queue_max"] >= 1
    # the LARGEST burst of the last five minutes, not the newest ...
    rig.run_second(path, 20, T0 + 9)
    assert len(rig.agent._herds) == 2
    assert rig.agent.metrics_snapshot()["herd_n"] == 32
    # ... and a burst older than that is gone
    rig.now[0] += agent_mod.HERD_KEEP_S + 1
    assert "herd_n" not in rig.agent.metrics_snapshot()


def test_a_slow_run_counts_its_write_back(rig):
    """``avg_time_writebacks_total`` counts the executions that got as
    far as the get + CAS of the job document."""
    real = rig.executor.run_once

    def slow(command, user="", timeout=0, env=None):
        res = real(command, user, timeout, env)
        res.end_ts = res.begin_ts + 0.5
        return res

    rig.executor.run_once = slow
    rig.run_second("common", 3, T0 + 5)
    assert rig.agent.metrics_snapshot()["avg_time_writebacks_total"] == 3
    assert len(ring(rig.agent, "avg_time")) == 3


# ---------------------------------------------------------------------------
# many producers, one commit each
# ---------------------------------------------------------------------------

def test_no_commit_is_lost_with_more_workers_than_cores(rig, herd_lines):
    """Three burst seconds of 40 on both paths at once, 64 pool threads
    and a 10 µs switch interval: every execution is in exactly one
    record and every ring holds every sample."""
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for k in range(3):
            rig.order("common", 20, T0 + 5 + k)
            rig.order("bundle", 20, T0 + 5 + k)
        rig.now[0] = T0 + 8.25
        rig.wait_recorded()
    finally:
        sys.setswitchinterval(old)
    herds = [json.loads(ln[5:]) for ln in herd_lines
             if ln.startswith("herd ")]
    assert sorted(r["sec"] for r in herds) == [T0 + 5, T0 + 6, T0 + 7]
    assert [r["n"] for r in herds] == [40, 40, 40]
    assert {len(ring(rig.agent, n)) for n in STAGES + ("whole",)} == {120}
    assert len(ring(rig.agent, "bundle_claim")) == 3
    for r in herds:
        tiled = sum(r[f"sum_{name}_ms"] for name in STAGES)
        assert tiled == pytest.approx(r["sum_whole_ms"], abs=0.05)
    assert rig.agent._probes_armed == 0 and not rig.agent._seconds
    deadline = time.monotonic() + 1.0
    while probe_threads() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not probe_threads()
