"""The rest of a run with the timed path broken underneath: the look
for a chip is skipped (``need_chip=False``), a real fleet runs at the
rehearsal size on the CPU, a saboteur alters what the scheduler
published while the window is open, and ``correct`` comes out false.

The faults this kind of cell can have: an answer altered where it is
produced (an order that was never due appears and is executed), and
part of the work left out — an order's fence is taken before its agent
claims it, so the fire never runs; an agent stops claiming, so its
orders stay in the store; an Alone job's lock is held after its first
run, so its later fires are skipped as "behind a live run" and leave
nothing behind.  A state returned unchanged, half a batch and an
exchange between chips belong to training cells and four-chip cells;
these cells have neither.

About 45 s each (the last two 80-130 s): real processes, a 14 s window.
"""

import argparse
import json
import signal
import threading
import time

import numpy as np
import pytest

import reference
import run as bench_run
import seeder

ARGS = dict(workload="rehearsal", trace=0,
            config_file="benchmarks/tests/data/rehearsal-2k-64.json",
            traffic="minute", controls=False, keep="", rehearse=True)


def drop_an_order(store, ks, info):
    """Take the fence of one Interval member of a live node's (node,
    second) order before the second comes: the agent's claim loses and
    the fire never runs."""
    fleet, conn = info["fleet"], store.clone()

    def run():
        deadline = time.time() + 12
        while time.time() < deadline:
            for nid in info["live"]:
                for kv in conn.get_prefix(f"{ks.dispatch}{nid}/"):
                    sec = kv.key.rsplit("/", 1)[1]
                    if not sec.isdigit() or not \
                            info["s0"] <= int(sec) < info["s1"] \
                            or int(sec) < time.time() + 0.7:
                        continue
                    for r in json.loads(kv.value):
                        job = bench_run.job_index(r.rpartition("/")[2]) \
                            if isinstance(r, str) else -1
                        if job >= 0 and fleet.kinds[job] \
                                == seeder.KIND_INTERVAL:
                            conn.put(ks.lock_key(fleet.job_id(job),
                                                 int(sec)), "intruder@0",
                                     lease=conn.grant(120))
                            conn.close()
                            return
            time.sleep(0.05)
        conn.close()
    threading.Thread(target=run, daemon=True).start()


def add_an_order(store, ks, info):
    """Publish a Common order for a (job, second) that is not due, for a
    job a live node is eligible for."""
    fleet = info["fleet"]
    live = [fleet.node_ids.index(n) for n in info["live"]]
    due = reference.due_matrix(fleet, info["s0"], info["s1"])
    gm = reference.group_matrix(fleet)
    for job in np.flatnonzero(fleet.kinds == seeder.KIND_COMMON):
        job = int(job)
        if any(reference.eligible(fleet, gm, job, n) for n in live):
            for c in range(2, due.shape[1]):
                if not due[job, c]:
                    store.put(ks.dispatch_all_key(
                        info["s0"] + c, seeder.JOB_GROUP,
                        fleet.job_id(job)), "{}", lease=store.grant(120))
                    return
    raise AssertionError("no Common job is eligible on a live node")


def stop_an_agent(store, ks, info):
    """A live agent stops claiming (SIGSTOP) as the window opens: the
    orders the scheduler has published to it stay in the store."""
    p, _log = info["procs"].procs[f"node-{info['live'][0]}"]
    p.send_signal(signal.SIGSTOP)


def hold_alone_locks(store, ks, info):
    """Every Alone job pinned to a live node has its lifetime lock
    taken as the window opens, after the job's earlier runs have ended:
    the agent skips each later fire as behind a live previous run, and
    leaves no order, no fence and no record of it."""
    fleet = info["fleet"]
    live = {fleet.node_ids.index(n) for n in info["live"]}
    lease = store.grant(300)
    held = 0
    for job in np.flatnonzero(fleet.kinds == seeder.KIND_ALONE):
        if fleet.group_of[job] < 0 and int(fleet.node_of[job]) in live:
            store.put(ks.alone_lock_key(fleet.job_id(int(job))),
                      "intruder", lease=lease)
            held += 1
    assert held


@pytest.mark.parametrize("sabotage,kind,fault,seconds", [
    (None, None, None, 14.0),
    (drop_an_order, "lost", "claimed_not_run", 14.0),
    (add_an_order, "spurious", "broadcast_not_due", 14.0),
    (stop_an_agent, "lost", "order_unclaimed", 14.0),
    # 24 judged seconds from :49-:53: every live node's */16 Alone job
    # ran at :48, before the window, and is due again at :00
    (hold_alone_locks, "lost", "alone_missing", 30.0)])
def test_a_broken_timed_path_is_not_correct(sabotage, kind, fault, seconds,
                                            monkeypatch):
    monkeypatch.setattr(bench_run, "WAIT_PAST_CLOSE_S", 12.0)
    args = argparse.Namespace(seed=2_200_000_033, **{**ARGS,
                                                     "seconds": seconds})
    line = bench_run.run_cell(args, need_chip=False, sabotage=sabotage)
    assert line["attempted"] > 100
    if kind is None:
        assert line["correct"] and line["failed"] == 0
        assert line["faults"] == {}
        return
    assert not line["correct"], line["checks"]
    assert line["checks"][kind]["value"] > 0
    assert line["faults"].get(fault, 0) > 0, line["faults"]
    assert line["failed"] == sum(line["checks"][k]["value"]
                                 for k in ("lost", "spurious"))
    if fault == "alone_missing":
        excess = line["checks"]["alone_excess_s"]
        assert excess["value"] > excess["limit"] == 1.0
