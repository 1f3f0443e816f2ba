"""KindAlone has one judge: the node, at the fire's second (upstream's
rule, ``job.go:243-271``).  The scheduler orders every due Alone fire;
what a lock said one or two windows earlier, while the window was
built, decides nothing.

The witness, in one process on a clock the test holds (memstore,
scheduler, one agent): an Alone job every 6 s whose run lasts ≈ 2 s.
The window that holds the job's next fire is built while the run is
live; the run ends before that fire's second; the fire runs.  (A
scheduler that mirrored the lock left the fire out of the window: no
order, no fence, no record — ``bj21299`` of PERF.md §7 row 1.)  A run
that does outlast its period is skipped by the node, counted there, and
its order's reservation goes back to the scheduler.
"""

import threading
import time

from cronsun_tpu.core import Job, JobRule, Keyspace
from cronsun_tpu.logsink import JobLogStore
from cronsun_tpu.node.agent import NodeAgent
from cronsun_tpu.node.executor import ExecResult
from cronsun_tpu.sched import SchedulerService
from cronsun_tpu.store import MemStore

KS = Keyspace()
WINDOW_S = 4
PERIOD_S = 6


class HeldRun:
    """An executor whose run lasts until the test lets it go."""

    def __init__(self, clock):
        self.clock = clock
        self.started = threading.Semaphore(0)
        self.let_go = threading.Event()

    def run_job(self, **kw):
        begin = self.clock()
        self.started.release()
        assert self.let_go.wait(30), "the test never ended the run"
        self.let_go.clear()
        return ExecResult(success=True, output="", begin_ts=begin,
                          end_ts=self.clock())


def test_fire_whose_window_was_built_during_the_previous_run_runs():
    t = [1_753_999_974.0]           # 6 s before a minute: */6 fires
    assert int(t[0] + 6) % 60 == 0

    def clock():
        return t[0]
    store, sink = MemStore(), JobLogStore()
    runs = HeldRun(clock)
    agent = NodeAgent(store, sink, node_id="n0", clock=clock,
                      executor=runs)
    agent.register()
    job = Job(id="wj", name="wj", group="g", command="sleep 2", kind=1,
              rules=[JobRule(id="r", timer=f"*/{PERIOD_S} * * * * *",
                             nids=["n0"])])
    job.check()
    store.put(KS.job_key("g", "wj"), job.to_json())
    sched = SchedulerService(store, job_capacity=64, node_capacity=8,
                             window_s=WINDOW_S, node_id="wit-sched",
                             pipelined=False, clock=clock)
    fires = [int(t[0]) + PERIOD_S * (i + 1) for i in range(3)]

    def order_of(sec):
        return store.get(KS.dispatch_bundle_key("n0", sec))

    def fence_of(sec):
        return store.get(KS.lock_key("wj", sec))

    def build_through(sec):
        """Step the scheduler until the window holding ``sec`` is out."""
        while sched.publisher.published_through <= sec:
            sched.step(now=sched._next_epoch or int(t[0]))
            # a new leader lists its mirrors afresh off the step thread
            # and installs the listing at its next step: let it end
            # first, so it is of the store before this test's writes
            time.sleep(0.05)
        agent.poll()

    def arrive(sec, at=0.0):
        t[0] = sec + at

    # the first fire: ordered, runs, and its run stays live
    build_through(fires[0])
    assert order_of(fires[0]) is not None
    arrive(fires[0])
    assert runs.started.acquire(timeout=20), "the first fire never ran"
    assert store.get(KS.alone_lock_key("wj")).value == "n0"
    # the window holding the second fire is built NOW, the lock live
    build_through(fires[1])
    assert order_of(fires[1]) is not None, \
        "a fire was left out for what its lock said a window earlier"
    # the run ends after 2 s; the second fire's second is 4 s away
    arrive(fires[0], at=2.0)
    runs.let_go.set()
    deadline = time.monotonic() + 20
    while store.get(KS.alone_lock_key("wj")) is not None:
        assert time.monotonic() < deadline, "the lock outlived its run"
        time.sleep(0.01)
    arrive(fires[1])
    assert runs.started.acquire(timeout=20), "the second fire never ran"
    assert fence_of(fires[1]) is not None
    assert agent.stats["alone_skipped_total"] == 0
    # this run outlasts its period: the third fire is ordered all the
    # same, skipped on the node behind the live run, takes no fence, and
    # gives back what its order reserved
    build_through(fires[2])
    assert order_of(fires[2]) is not None
    sched.drain_watches()
    assert sched._excl_cnt.get("n0", 0) >= 1
    arrive(fires[2])
    deadline = time.monotonic() + 20
    while agent.stats["alone_skipped_total"] < 1:
        assert time.monotonic() < deadline, "the third fire was not judged"
        time.sleep(0.01)
    arrive(fires[2], at=1.0)
    runs.let_go.set()
    agent.join_running(timeout=20)
    assert fence_of(fires[2]) is None, "a skipped fire took its fence"
    assert order_of(fires[2]) is None
    sched.drain_watches()
    assert sched._excl_cnt.get("n0", 0) == 0, \
        "the skipped order still counts against its node"
    _recs, total = sink.query_logs(job_ids=["wj"])
    assert total == 2, "the first two fires ran, the third did not"
    assert sched.metrics_snapshot()["alone_left_out_total"] == 0
    assert agent.metrics_snapshot()["alone_skipped_total"] == 1
    agent.stop()
    sched.stop()
    store.close()
