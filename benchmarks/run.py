#!/usr/bin/env python3
"""One cell of BENCHMARK.json, once, in a fresh fleet.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s>
                              --trace <0|1>

The served path through the normal entry points: ``bin.store --native``,
``bin.logd``, N ``bin.node`` agents and ``bin.sched`` (started through
``sched_launcher.py``, which calls its ``main()`` unchanged; the only
process that touches JAX — this one never imports it).  The cell's
configuration (``configs/<config>.json``), traffic (``traffic/
<traffic>.json``) and per-layer metrics (``metrics/<name>.py``) are
found by the names in BENCHMARK.json; README.md says how to add one.

Order of a run: servers, seed (``put_many`` from --seed), agents (and
``placeholders.py`` for the nodes that run none), scheduler cold load as a warm standby (this process holds the leader
key), align (release the key at a second of the minute that puts the
traffic's minute boundary inside the judged seconds), first published
window (= end of set-up), hold, the timed window of --seconds, stop
the scheduler, wait for the executions of the judged seconds, compare
everything with the plain reference (reference.py), print the line.
"""

import argparse
import importlib.util
import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(1, REPO)      # the system under test's client modules

import numpy as np  # noqa: E402

import reference  # noqa: E402
import seeder  # noqa: E402
from placeholders import Placeholders  # noqa: E402
from procs import Fleet, RunFailure, tail  # noqa: E402

SCHED_ID = "scheduler-1"
LOSS_COUNTERS = ("skipped_seconds_total", "overflow_drops_total",
                 "publish_failures", "publish_abandoned")
TRACE_SECONDS = 8.0        # two plan windows of window_s 4
WAIT_PAST_CLOSE_S = 60.0   # an answer that comes late is late, not wrong
QUIET_S = 3.0              # no new record for this long: the agents are idle
LIST_PAGE = 5_000          # keys a listing holds the store's stripes for


def say(msg: str):
    print(msg, flush=True)


class Phases:
    def __init__(self):
        self.t0 = time.time()
        self.seconds = {}

    def done(self, name: str, detail: str = ""):
        now = time.time()
        self.seconds[name] = now - self.t0
        say(f"phase {name}: {now - self.t0:.1f}s"
            + (f" — {detail}" if detail else ""))
        self.t0 = now


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(workload: str, config_file: str = "", traffic: str = ""):
    """(bench, cell, config, traffic dict).  ``config_file``/``traffic``
    name an unlisted pair (a replay or a sweep), never a cell."""
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    if config_file:
        cfg = load_json(os.path.join(REPO, config_file))
        cell = {"name": workload, "config": cfg["name"],
                "traffic": traffic, "chips": cfg["chips"]}
    else:
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                             f"(has {sorted(cells)})")
        cell = cells[workload]
        by_name = {c["name"]: c for c in bench["configs"]}
        cfg = load_json(os.path.join(REPO, by_name[cell["config"]]["file"]))
    tr = load_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json"))
    return bench, cell, cfg, tr


def cell_metrics(bench: dict, cell: str, section: str) -> list:
    return [m for m in bench[section]
            if cell in m.get("workloads", [cell])]


def read_metric(name: str, run: dict):
    """``metrics/<name>.py`` ``read(run)`` -> a number, or None where it
    finds nothing to read."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("metric_" + name.replace(
        ".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


# ---------------------------------------------------------------------------
# the window's place in the minute
# ---------------------------------------------------------------------------

def judged_length(seconds: float, rule: dict, window_s: int) -> int:
    whole = int(seconds - 0.5 - rule["judged_tail_s"] + 0.5)
    return max(window_s, whole // window_s * window_s)


def lead_second(now: float, judged: int, rule: dict,
                window_s: int) -> int:
    """The earliest whole second >= now + 1 at which to let the
    scheduler lead.  It is a multiple of window_s (so plan windows sit
    alike in every run); where the judged stretch is long enough to
    hold a minute boundary with room after it, the boundary's offset
    from the first judged second is inside the rule's band."""
    first_off = int(rule["lead_to_open_s"] + 0.5)       # lead -> s0
    lo, hi = rule["boundary_offsets_s"]
    hi = min(hi, judged - rule["boundary_room_after_s"])
    t = (int(now) + 1 + window_s) // window_s * window_s
    if rule["boundary"] != "one" or hi < lo:
        return t
    for cand in range(t, t + 120, window_s):
        off = -(cand + first_off) % 60      # s0 -> next :00
        if lo <= off <= hi:
            return cand
    raise RunFailure(f"no lead second puts :00 in {lo}..{hi}")


# ---------------------------------------------------------------------------
# what the run left behind -> reference.Observed
# ---------------------------------------------------------------------------

def job_index(job_id: str) -> int:
    return int(job_id[2:]) if job_id[:2] == "bj" and job_id[2:].isdigit() \
        else -1


def node_index(node_id: str) -> int:
    return int(node_id[2:]) if node_id[:2] == "bn" and node_id[2:].isdigit() \
        else -1


def parse_orders(ks, items, s0: int, s1: int):
    """(broadcasts, exclusive orders) of the judged seconds among the
    (key, value) pairs of dispatch keys."""
    bcast, orders = [], []
    pfx = ks.dispatch
    for key, value in items:
        parts = key[len(pfx):].split("/")
        head = ks.split_bundle_epoch(parts[1]) if len(parts) > 1 else None
        if head is None or not s0 <= head[0] < s1:
            continue
        sec = head[0]
        if parts[0] == ks.BROADCAST:
            bcast.append((job_index(parts[-1]), sec))
        elif len(parts) == 2:
            for ref in json.loads(value):
                if isinstance(ref, str):    # a dict is the trace header
                    orders.append((node_index(parts[0]),
                                   job_index(ref.rpartition("/")[2]), sec))
        else:
            orders.append((node_index(parts[0]), job_index(parts[-1]), sec))
    return bcast, orders


def read_orders(store, ks, consumed: dict, s0: int, s1: int):
    """Common orders are never deleted (they lapse with their lease);
    exclusive ones are deleted by the agent that claims them, or by
    ``placeholders.py`` in the place of an agent: what it consumed,
    and whatever is still in the store (an order nobody claimed)."""
    left = {kv.key: kv.value
            for kv in store.get_prefix_paged(ks.dispatch, LIST_PAGE)}
    return parse_orders(ks, {**consumed, **left}.items(), s0, s1)


def read_fences(store, ks, s0: int, s1: int) -> list:
    out = []
    for kv in store.get_prefix_paged(ks.lock, LIST_PAGE):
        jid, _, sec = kv.key[len(ks.lock):].rpartition("/")
        if sec.isdigit() and s0 <= int(sec) < s1 and "/" not in jid:
            out.append((node_index(kv.value.partition("@")[0]),
                        job_index(jid), int(sec)))
    return out


class Records:
    """Result records of the live agents, read back through logd."""

    def __init__(self, sink, live_ids: list, s0: int, s1: int,
                 alone_jobs: set):
        self.sink, self.s0, self.s1 = sink, s0, s1
        self.cursors = {nid: 0 for nid in live_ids}
        self.judged = []            # (node, job, second, ok, begin_ts)
        self.alone_jobs = alone_jobs
        self.alone_runs = {}        # job -> [(second, begin_ts, end_ts)]
        self.others = 0

    def pull(self):
        for nid, cur in self.cursors.items():
            while True:
                recs, _ = self.sink.query_logs(node=nid, after_id=cur,
                                               page_size=500)
                for r in recs:
                    cur = max(cur, r.id)
                    sec = r.output.strip()
                    job = job_index(r.job_id)
                    if not sec.isdigit():
                        # the command could not say its second: a run
                        # that failed, judged wherever it belongs
                        sec = str(int(r.begin_ts))
                        r.success = False
                    sec = int(sec)
                    if job in self.alone_jobs:
                        self.alone_runs.setdefault(job, []).append(
                            (sec, float(r.begin_ts), float(r.end_ts)))
                    if self.s0 <= sec < self.s1:
                        self.judged.append((node_index(nid), job, sec,
                                            bool(r.success),
                                            float(r.begin_ts)))
                    else:
                        self.others += 1
                if len(recs) < 500:
                    break
            self.cursors[nid] = cur


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def request(ctl: str, name: str, body: dict):
    tmp = os.path.join(ctl, name + ".tmp")
    with open(tmp, "w") as f:
        json.dump(body, f)
    os.replace(tmp, os.path.join(ctl, name + ".req"))


def await_answer(ctl: str, name: str, timeout: float):
    path = os.path.join(ctl, name + ".done")
    deadline = time.time() + timeout
    while time.time() < deadline:
        if os.path.exists(path):
            return load_json(path)
        time.sleep(0.1)
    return {"error": f"no answer to {name} within {timeout:.0f}s"}


def keep_files(dest: str, work: str, s0: int, obs):
    """--keep: the children's logs, the trace, every judged execution's
    (node, job, second into the judged stretch, lag) and every
    exclusive order's (node, job, second into it)."""
    os.makedirs(dest, exist_ok=True)
    with open(os.path.join(dest, "lags.json"), "w") as f:
        json.dump([[node, job, sec - s0, begin - sec]
                   for node, job, sec, _ok, begin in obs.records], f)
    with open(os.path.join(dest, "orders.json"), "w") as f:
        json.dump([[node, job, sec - s0] for node, job, sec in obs.orders],
                  f)
    with open(os.path.join(dest, "alone_runs.json"), "w") as f:
        json.dump({job: [[sec - s0, begin - s0, end - s0]
                         for sec, begin, end in runs]
                   for job, runs in obs.alone_runs.items()}, f)
    for root, _d, files in os.walk(work):
        for fn in files:
            if fn.endswith((".log", ".xplane.pb")):
                shutil.copy(os.path.join(root, fn), dest)


def run_cell(args, need_chip: bool = True, sabotage=None) -> dict:
    """Drive one run; returns the result line as a dict.  ``sabotage``
    (tests only) is called as sabotage(store, ks, fleet_info) once the
    window is open, with the timed path running underneath it."""
    from cronsun_tpu.core import Keyspace
    from cronsun_tpu.logsink import RemoteJobLogStore
    from cronsun_tpu.store import RemoteStore

    bench, cell, cfg, traffic = load_cell(args.workload, args.config_file,
                                          args.traffic)
    rule = traffic["window"]
    window_s = int(cfg["window_s"])
    n_jobs, n_nodes = int(cfg["jobs"]), int(cfg["nodes"])
    judged = judged_length(args.seconds, rule, window_s)
    job_capacity = 1 << max(10, (n_jobs - 1).bit_length())
    say(f"benchmark: {cell['name']} = {n_jobs} jobs x {n_nodes} nodes x "
        f"{cfg['live_agents']} live agents, traffic {cell['traffic']}, "
        f"seed {args.seed}, {args.seconds}s ({judged} judged), "
        f"trace {args.trace}")
    ph = Phases()
    ph.t0 = T_START
    ks = Keyspace()
    work = tempfile.mkdtemp(prefix="cronsun-bench-")
    ctl = os.path.join(work, "ctl")
    os.makedirs(ctl)
    fleet = Fleet(work)
    store = sink = stand_ins = None
    try:
        # ---- store + result store -----------------------------------
        fleet.spawn("store", ["-m", "cronsun_tpu.bin.store", "--native",
                              "--port", "0"])
        fleet.spawn("logd", ["-m", "cronsun_tpu.bin.logd", "--port", "0",
                             "--db", os.path.join(work, "logd.db")])
        store_addr = fleet.await_line("store", "READY", 180)
        logd_addr = fleet.await_line("logd", "READY", 60)
        host, _, port = store_addr.rpartition(":")
        store = RemoteStore(host, int(port), timeout=600)
        lhost, _, lport = logd_addr.rpartition(":")
        sink = RemoteJobLogStore(lhost, int(lport), timeout=120)
        ph.done("servers", f"store {store_addr} (native), logd {logd_addr}")

        # ---- seed ------------------------------------------------------
        drawn = seeder.draw(traffic, n_jobs, n_nodes, args.seed,
                            int(time.time()), cfg["live_group_memberships"])
        live = drawn.live
        live_ids = [drawn.node_ids[n] for n in live]
        nodes_i, groups_i, jobs_i, phases_i = seeder.documents(
            drawn, ks.prefix)
        # a real agent refuses an id another host name holds: the live
        # ids are not registered as placeholders
        store.put_many([kv for kv in nodes_i
                        if kv[0].rsplit("/", 1)[1] not in live_ids])
        store.put_many(groups_i)
        for items in (jobs_i, phases_i):
            for at in range(0, len(items), 20_000):
                store.put_many(items[at:at + 20_000])
        n_cmd = store.count_prefix(ks.cmd)
        if n_cmd != n_jobs:
            raise RunFailure(f"seeded {n_cmd} jobs, wanted {n_jobs}")
        member_of = {nid: sum(n in g for g in drawn.groups)
                     for n, nid in zip(live, live_ids)}
        stand_ins = Placeholders(store, ks, live_ids)
        ph.done("seed", f"{n_cmd} jobs, {len(groups_i)} groups, "
                        f"{n_nodes - len(live)} placeholder nodes (their "
                        f"orders consumed by placeholders.py); live "
                        f"ids and their group memberships {member_of}")

        # ---- conf + agents (registered before the scheduler loads) ----
        conf = os.path.join(work, "conf.json")
        with open(conf, "w") as f:
            # the TTLs are the configuration's, stated to every process
            # and not left to the program's defaults
            json.dump({"job_capacity": job_capacity,
                       "node_capacity": n_nodes, "window_s": window_s,
                       "log_addr": logd_addr,
                       "log_db": os.path.join(work, "unused.db"),
                       **{k: cfg["ttl"][k] for k in
                          ("node_ttl", "lock_ttl", "proc_ttl")}}, f)
        for nid in live_ids:
            fleet.spawn(f"node-{nid}", [
                "-m", "cronsun_tpu.bin.node", "--store", store_addr,
                "--conf", conf, "--node-id", nid])
        for nid in live_ids:
            fleet.await_line(f"node-{nid}", "READY", 120)
        ph.done("agents", f"{len(live_ids)} bin.node agents READY")

        # ---- the scheduler, as a warm standby --------------------------
        # this process holds the leader key, so the scheduler loads,
        # warms its plan executables and waits; letting go of the key
        # at a chosen second is what places the window in the minute
        hold = store.grant(3600)
        if not store.put_if_absent(ks.leader, "benchmark-hold", lease=hold):
            raise RunFailure("the leader key is already held")
        t_spawn = time.time()
        fleet.spawn("sched", [os.path.join(HERE, "sched_launcher.py"), ctl,
                              "--store", store_addr, "--conf", conf,
                              "--node-id", SCHED_ID])
        dev = json.loads(fleet.await_line("sched", "benchdevice", 300))
        if need_chip and (dev["platform"] != "tpu"
                          or dev["count"] < cell["chips"]):
            raise RunFailure(f"no accelerator for this cell (wants "
                             f"{cell['chips']} TPU chip(s)): JAX reports "
                             f"{dev}")
        fleet.await_line("sched", "READY", 900)
        cold_load_s = time.time() - t_spawn
        ph.done("cold_load", f"sched spawn -> READY; device {dev}")
        deadline = time.time() + 900
        while not fleet.log_has("sched", "plan executables warmed") \
                and not fleet.log_has("sched", "background plan warm failed"):
            fleet.check_alive()
            if time.time() > deadline:
                raise RunFailure("the standby never warmed its plan "
                                 "executables:\n"
                                 + tail(fleet.procs["sched"][1]))
            time.sleep(0.1)
        ph.done("warm", "standby: plan executables compiled or loaded")

        # ---- align, lead, first published window ----------------------
        lead = lead_second(time.time() + 0.3, judged, rule, window_s)
        t_align = time.time()
        time.sleep(max(0.0, lead + 0.05 - time.time()))
        store.revoke(hold)
        align_s = time.time() - t_align
        ph.done("align", f"slept {align_s:.1f}s to let go of the leader "
                         f"key at second :{lead % 60:02d} of the minute")
        hwm_seen = []                    # (published_through, seen at)

        def poll_hwm():
            kv = store.get(ks.hwm)
            if kv is not None and kv.value.isdigit():
                v = int(kv.value)
                if not hwm_seen or v > hwm_seen[-1][0]:
                    hwm_seen.append((v, time.time()))
            return hwm_seen[-1][0] if hwm_seen else 0

        deadline = time.time() + 300
        while poll_hwm() < lead + 1 + window_s:
            fleet.check_alive()
            if time.time() > deadline:
                raise RunFailure("no window was published within 300s:\n"
                                 + tail(fleet.procs["sched"][1]))
            time.sleep(0.05)
        t_first = hwm_seen[-1][1]
        setup_s = t_first - T_START - align_s
        ph.done("first_publish", f"published through {hwm_seen[-1][0]} "
                f"(:{hwm_seen[-1][0] % 60:02d}); set-up {setup_s:.1f}s = "
                f"process start -> here, less the {align_s:.1f}s slept")
        s0 = lead + int(rule["lead_to_open_s"] + 0.5)
        s1 = s0 + judged
        t_open = s0 - 0.5
        boundary_off = -s0 % 60
        while time.time() < t_open:
            poll_hwm()
            time.sleep(min(0.25, max(0.0, t_open - time.time())))
        ph.done("hold", f"window opens at second :{t_open % 60:04.1f}; "
                f"judged seconds [{s0}, {s1}) from :{s0 % 60:02d}, the "
                f"minute boundary {boundary_off}s in"
                + ("" if boundary_off < judged else " (NOT inside)"))

        # ---- the timed window -------------------------------------------
        t_open = time.time()
        ops_open, cpu_open = store.op_stats(), fleet.cpu_seconds()
        if args.trace:
            request(ctl, "trace", {"op": "trace",
                                   "dir": os.path.join(work, "trace"),
                                   "start_at": t_open + 1.0,
                                   "seconds": TRACE_SECONDS})
        if sabotage is not None:
            sabotage(store, ks, {"s0": s0, "s1": s1, "live": live_ids,
                                 "fleet": drawn, "procs": fleet})
        while time.time() < t_open + args.seconds:
            fleet.check_alive()
            poll_hwm()
            time.sleep(min(0.25, max(0.0, t_open + args.seconds
                                     - time.time())))
        t_close = time.time()
        ops_close, cpu_close = store.op_stats(), fleet.cpu_seconds()
        request(ctl, "memory", {"op": "memory"})
        kv = store.get(ks.metrics_key("sched", SCHED_ID))
        snap = json.loads(kv.value) if kv is not None else {}
        ph.done("window", f"{t_close - t_open:.1f}s")

        # ---- late is late, not wrong: the judged seconds get published
        while poll_hwm() < s1 and time.time() < t_close + 30:
            fleet.check_alive()
            time.sleep(0.1)
        last_pub = next((t for v, t in hwm_seen if v >= s1), None)
        say("scheduler lateness: second "
            f"{s1 - 1} was published "
            + (f"{last_pub - (s1 - 1):+.1f}s after it was due (negative = "
               f"ahead of real time)" if last_pub is not None
               else "NEVER (30s past the close)"))
        mem = await_answer(ctl, "memory", 20)
        trace_info = await_answer(ctl, "trace", 30) if args.trace else None
        say("sched metrics: " + json.dumps({k: snap.get(k) for k in (
            "steps_total", "dispatches_total", "jobs", "sched_step_p50_ms",
            "sched_step_p99_ms", "tick_p50_ms", "tick_p99_ms",
            "publish_window_ms", "overflow_late_fires_total",
            "pipeline_stalls_total", "lease_resigns_total",
            *LOSS_COUNTERS)}))
        say("sched step spans p50 ms: " + json.dumps({
            k[len("step_span_"):-len("_p50_ms")]: v
            for k, v in snap.items()
            if k.startswith("step_span_") and k.endswith("_p50_ms")}))
        on_jax = [n for n in fleet.procs if fleet.maps_jax(n)]
        rcs = fleet.stop("sched", 15)
        ph.done("sched_stop", f"exit {rcs['sched']}")

        # ---- host health: causes, said here, judged by what they cost
        lapses = sum(fleet.log_has(f"node-{nid}", "node lease lapsed")
                     for nid in live_ids)
        causes = {
            "jobs_loaded": snap.get("jobs"),
            "loss_counters": {k: snap.get(k) for k in LOSS_COUNTERS
                              if snap.get(k)},
            "lease_lapses": lapses,
            "sched_log_errors": fleet.log_has("sched",
                                              "scheduler step failed")
            + fleet.log_has("sched", "Traceback (most recent"),
            "jax_mapped_in": on_jax,
            "this_process_imported_jax": "jax" in sys.modules,
        }
        say("host health (causes; what they cost is compared below): "
            + json.dumps(causes))

        # ---- executions of the judged seconds ---------------------------
        # The store is read back ONCE, and only when the agents have gone
        # quiet: a listing holds every stripe it touches, and a listing
        # of 100k orders under agents still draining a herd stalled
        # their puts past the Alone lock's 5 s lease (fires skipped:
        # the harness's own doing, seen at 100k).  Until then only the
        # records plane (logd) is polled.
        recs = Records(sink, live_ids, s0, s1, set(np.flatnonzero(
            drawn.kinds == seeder.KIND_ALONE).tolist()))
        quiet_since, seen = time.time(), -1
        while time.time() < t_close + WAIT_PAST_CLOSE_S:
            fleet.check_alive()
            recs.pull()
            n = len(recs.judged) + recs.others
            if n != seen:
                seen, quiet_since = n, time.time()
            elif time.time() - quiet_since >= QUIET_S:
                break
            time.sleep(0.5)

        consumed = stand_ins.stop()

        def read_back():
            bcast, orders = read_orders(store, ks, consumed, s0, s1)
            return reference.Observed(bcast, orders, recs.judged,
                                      read_fences(store, ks, s0, s1),
                                      recs.alone_runs)
        obs = read_back()
        verdict = reference.judge(drawn, live, s0, s1, obs)
        while verdict.lost and time.time() < t_close + WAIT_PAST_CLOSE_S:
            # an order not yet claimed, or claimed and not yet run: wait
            # for it, a minute past the close
            time.sleep(3.0)
            recs.pull()
            obs = read_back()
            verdict = reference.judge(drawn, live, s0, s1, obs)
        bcast, orders = obs.broadcasts, obs.orders
        ph.done("consume", f"{len(recs.judged)} executions of the judged "
                f"seconds recorded ({recs.others} of other seconds), "
                f"{len(bcast)} broadcasts, {len(orders)} exclusive order "
                f"members on placeholders or unclaimed ({len(consumed)} "
                f"order keys consumed in all, {stand_ins.watches_lost} "
                f"watches lost)")
        if args.controls:
            # the reference in the program's place, every guarantee kept
            # and then each broken in turn: printed, never in the result
            for name in ("",) + reference.CONTROLS:
                v = reference.judge(drawn, live, s0, s1,
                                    reference.reference_outcome(
                                        drawn, live, s0, s1, name))
                say(f"control {name or 'none (the reference itself)'}: "
                    f"lost {v.lost} spurious {v.spurious} {v.detail} "
                    f"alone (gap, lag) of the lost "
                    f"{v.alone_lost_gaps[:3]} -> correct {v.correct}")

        # ---- stop everything, then read the trace -----------------------
        sink.close()
        store.close()
        sink = store = None
        fleet.stop("node", 20)
        fleet.stop("logd", 10)
        fleet.stop("store", 10)
        ph.done("stop", "every child ended")
        if args.keep:
            keep_files(args.keep, work, s0, obs)
        trace = None
        if args.trace:
            if "error" in trace_info:
                raise RunFailure(f"the trace failed: {trace_info}")
            out = subprocess.run(
                [sys.executable, os.path.join(HERE, "tracereduce.py"),
                 os.path.join(work, "trace"),
                 str(trace_info["stopped"] - trace_info["started"])],
                capture_output=True,
                text=True, timeout=240,
                env={**os.environ, "JAX_PLATFORMS": "cpu"})
            if out.returncode == 0:
                trace = json.loads(out.stdout.strip().splitlines()[-1])
                ph.done("trace_reduce", f"{trace['window_s']:.2f}s traced, "
                        f"{trace['busy_s'] * 1e3:.1f} ms busy")
            elif need_chip:
                raise RunFailure("trace reduction failed:\n" + out.stderr)
            else:
                say("rehearsal: no device plane to reduce: "
                    + out.stderr.strip()[-200:])
    finally:
        if stand_ins is not None:
            stand_ins.stop()
        for c in (sink, store):
            if c is not None:
                try:
                    c.close()
                except Exception:  # noqa: BLE001 — the server may be gone
                    pass
        fleet.kill_all()
        shutil.rmtree(work, ignore_errors=True)
    if "jax" in sys.modules:
        raise RunFailure("this process imported jax")

    # ---- the numbers ------------------------------------------------------
    lags = np.sort(verdict.lags)
    if not len(lags):
        raise RunFailure("no execution of a judged second was recorded")
    p50, p95, p99 = (float(np.percentile(lags, q)) for q in (50, 95, 99))
    say(f"fire lag, s, over the {len(lags)} executions of the judged "
        f"seconds: p50 {p50:.3f} p95 {p95:.3f} p99 {p99:.3f} max "
        f"{lags[-1]:.3f} mean {lags.mean():.3f} ({int(len(lags) * 0.01)} "
        f"samples beyond p99)")
    by_win = {}
    for _n, _j, sec, _ok, begin in obs.records:
        by_win.setdefault((sec - s0) // window_s * window_s, []).append(
            begin - sec)
    say("fire lag by plan window (offset from the first judged second: "
        "executions, median s, max s): " + "; ".join(
            f"+{k}: {len(v)} {statistics.median(v):.1f} {max(v):.1f}"
            for k, v in sorted(by_win.items())))
    gaps = verdict.alone_gaps

    def pairs(xs):
        return [(round(g, 2), round(lag, 2)) for g, lag in xs]
    say(f"alone fires skipped behind a live previous run: {len(gaps)}; "
        f"(the second less that run's end, that run's lag), s, held to "
        f"gap <= {reference.ALONE_RELEASE_S} + lag: {pairs(gaps)}; of "
        f"those counted lost: {pairs(verdict.alone_lost_gaps)}")
    say(f"faults by kind: {verdict.detail}; examples {verdict.examples}")
    cpu = {n: cpu_close[n] - cpu_open.get(n, 0.0) for n in cpu_close}
    say(f"CPU seconds inside the window, by process (of "
        f"{os.cpu_count()} cores x {t_close - t_open:.0f}s): "
        + ", ".join(f"{n} {v:.1f}" for n, v in cpu.items()))
    run = {
        "cell": {"name": cell["name"], "jobs": n_jobs, "nodes": n_nodes,
                 "window_s": window_s, "live_agents": len(live_ids),
                 "job_capacity": job_capacity},
        "phases": ph.seconds, "cold_load_s": cold_load_s,
        "setup_s": setup_s, "snapshot": snap,
        "op_stats": {"open": ops_open, "close": ops_close},
        "cpu": cpu, "window_seconds": t_close - t_open,
        "agent_names": [f"node-{nid}" for nid in live_ids],
        "lease_lapses": lapses, "trace": trace, "device": dev,
        "judged_s": judged, "attempted": verdict.attempted,
        "lag_samples": len(lags),
        "fire_lag_p50_s": p50, "fire_lag_p95_s": p95,
        "fire_lag_p99_s": p99, "fire_lag_max_s": float(lags[-1]),
    }
    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, cell["name"], section) \
            if not args.config_file else bench[section]:
        v = read_metric(m["name"], run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": mem.get("peak_bytes")}
    line = {"correct": verdict.correct, "attempted": verdict.attempted,
            "failed": verdict.failed, "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = {"device_ops": trace["device_ops"][:10],
                             "idle_gaps": trace["idle_gaps"][:10]}
    line["phases"] = {k: round(v, 3) for k, v in ph.seconds.items()}
    line["lag_samples"] = len(lags)
    line["boundary_offset_s"] = boundary_off
    line["alone_skipped"] = len(gaps)
    line["faults"] = verdict.detail
    # the numbers compared, each beside its limit; comes last
    line["checks"] = {"lost": {"value": verdict.lost, "limit": 0},
                      "spurious": {"value": verdict.spurious, "limit": 0}}
    if verdict.alone_excess_s is not None:
        # what decides whether a traceless Alone fire is in ``lost``
        line["checks"]["alone_excess_s"] = {
            "value": min(verdict.alone_excess_s, 1e9),
            "limit": reference.ALONE_RELEASE_S}
    return line


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # not the driver's: an unlisted configuration (replay, sweep), the
    # control, the children's logs, and the CPU rehearsal
    ap.add_argument("--config-file", default="",
                    help="run this configuration file (relative to the "
                         "repo) under --traffic instead of a listed cell")
    ap.add_argument("--traffic", default="minute")
    ap.add_argument("--controls", action="store_true",
                    help="also judge the reference's own outcome, whole "
                         "and with each guarantee broken in turn "
                         "(printed, never in the result)")
    ap.add_argument("--keep", default="",
                    help="copy the children's logs and the trace here")
    ap.add_argument("--rehearse", action="store_true",
                    help="go on without a TPU; the line goes to standard "
                         "error and the exit code is 1")
    args = ap.parse_args()
    try:
        line = run_cell(args, need_chip=not args.rehearse)
    except RunFailure as e:
        print(f"FAILED after {time.time() - T_START:.1f}s:\n{e}",
              file=sys.stderr, flush=True)
        return 1
    say(f"run took {time.time() - T_START:.1f}s")
    checks = ", ".join(f"{k} {v['value']} (limit {v['limit']})"
                       for k, v in line["checks"].items())
    text = json.dumps(line)
    if line["device"]["platform"] != "tpu":
        print(f"rehearsal on {line['device']['platform']} (no result): "
              + text, file=sys.stderr, flush=True)
        print(f"correct {line['correct']}: {checks}", file=sys.stderr,
              flush=True)
        return 1
    print(text, flush=True)
    print(f"correct {line['correct']}: {checks}", file=sys.stderr,
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
