#!/usr/bin/env python3
"""chip_smoke.py — the served path on the chip, once, checked.

    python chip_smoke.py            # one chip: the fleet smoke
    python chip_smoke.py --mesh4    # four chips: the mesh phase only

With no option it drives the deployment of BASELINE.md config 5 —
1,000,000 jobs in the 2^20-row table x 10,240 registered nodes on one
v5e chip, ``window_s`` 4, the placement-realistic mix of
``scripts/bench_sched.py seed()`` — through the normal entry points:
``bin.store --native`` (the C++ server, which bin.store builds from the
committed ``native/stored.cc`` when no current binary is there; the
Python server cannot carry this fleet), ``bin.logd`` (the networked
result store, Python; agents record through it, not a shared SQLite
file),
``bin.sched`` (the plain TickPlanner; the ONLY process that touches
JAX) and 8 real ``bin.node`` agents running under 8 of the seeded node
ids.  This process never imports JAX.  It waits for the scheduler to
lead, for 5 consecutive windows (20 scheduled seconds) to be planned,
published and consumed, and holds the single-node jobs pinned to the
live agents to the scalar cron engine (``cronsun_tpu/cron``): executed
(job, scheduled second) pairs == due pairs for kinds 0 and 2, a subset
without repeats for KindAlone.  The scheduled second is what each
execution was handed in ``CRONSUN_SCHEDULED_TS`` (the seeded command
prints it into its record); exclusive kinds are cross-checked against
the ``lock/<job>/<second>`` fence plane.  Every TTL is the deployment's
default, and an agent whose node lease lapses fails the run.  How late
the scheduler ran and how far the agents lagged is printed and held to
no limit: this script judges what ran, not when.

``--mesh4`` runs, in this process, ONLY the planners of a four-chip
host at 1M rows x 102,400 nodes: ``Sharded2DTickPlanner`` on
``make_mesh2d(2, 2)``, then ``ShardedTickPlanner`` on ``make_mesh(4)``.

The last line of standard output is the contract line
``{"ok": true, "device": {...}}`` and is printed only when every phase
passed AND the device that planned was a TPU.  ``--jobs/--nodes/
--agents`` exist for the CPU rehearsal (``JAX_PLATFORMS=cpu python
chip_smoke.py --jobs 2048 --nodes 64 --agents 2``): it runs every phase
and then fails on the device check alone.
"""

import argparse
import datetime
import json
import os
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
WINDOWS = 5                      # consecutive windows held to the reference
SCHED_ID = "scheduler-1"
LOSS_COUNTERS = ("skipped_seconds_total", "overflow_drops_total",
                 "publish_failures", "publish_abandoned")
UTC = datetime.timezone.utc


class SmokeFailure(Exception):
    """A phase could not complete; nothing after it can be judged."""


def say(msg: str):
    print(msg, flush=True)


class Phases:
    """Per-phase wall seconds, printed as each phase ends."""

    def __init__(self):
        self.t0 = time.time()

    def done(self, name: str, detail: str = ""):
        now = time.time()
        say(f"phase {name}: {now - self.t0:.1f}s"
            + (f" — {detail}" if detail else ""))
        self.t0 = now


def due_seconds(timer: str, anchor: int, s0: int, s1: int) -> set:
    """Seconds in [s0, s1) at which the scalar engine fires ``timer``:
    a cron spec by walking ``Schedule.next`` from s0-1, an ``@every``
    rule along its chain anchor, anchor+p, anchor+2p, ..."""
    from cronsun_tpu.cron import EverySpec, Schedule, parse
    sch = Schedule(parse(timer))
    t = s0 - 1
    if isinstance(sch.spec, EverySpec):
        p = max(1, sch.spec.period_s)
        t = anchor + (s0 - 1 - anchor) // p * p
    out = set()
    at = datetime.datetime.fromtimestamp(t, UTC)
    while True:
        at = sch.next(at)
        if at is None or at.timestamp() >= s1:
            return out
        out.add(int(at.timestamp()))


# ---------------------------------------------------------------------------
# one chip: store + logd + sched + agents
# ---------------------------------------------------------------------------

class Fleet:
    """The children, their log files, and their orderly end."""

    def __init__(self):
        os.makedirs(OUT_DIR, exist_ok=True)
        self.procs = []                  # (name, Popen, log path)
        self.stopped = set()             # names this script has ended

    def spawn(self, name: str, module: str, *args: str):
        path = os.path.join(OUT_DIR, f"{name}.log")
        with open(path, "wb") as log:
            # the environment goes through untouched: the scheduler
            # child finds its chip and its compile cache as JAX would
            p = subprocess.Popen(
                [sys.executable, "-m", module, *args], cwd=REPO,
                stdout=log, stderr=subprocess.STDOUT)
        self.procs.append((name, p, path))
        return p, path

    def await_ready(self, p, path: str, timeout: float) -> str:
        deadline = time.time() + timeout
        while time.time() < deadline:
            with open(path, errors="replace") as f:
                for line in f:
                    if line.startswith("READY") and line.endswith("\n"):
                        return line.split(None, 1)[1].strip()
            if p.poll() is not None:
                raise SmokeFailure(
                    f"{path} exited rc={p.returncode} before READY:\n"
                    + tail(path))
            time.sleep(0.2)
        raise SmokeFailure(f"no READY in {path} within {timeout:.0f}s:\n"
                           + tail(path))

    def check_alive(self):
        for name, p, path in self.procs:
            if p.poll() is not None and name not in self.stopped:
                raise SmokeFailure(f"{name} died rc={p.returncode}:\n"
                                   + tail(path))

    def imports_jax(self, p) -> bool:
        with open(f"/proc/{p.pid}/maps") as f:
            return "jaxlib" in f.read()

    def stop(self, *prefixes: str) -> dict:
        """SIGTERM the children whose names start with each prefix, one
        prefix after the other; returns {name: exit code}.  Whatever
        outlasts 240 s (a scheduler finishing its step, then draining
        its in-flight windows and replans into the store) is killed."""
        rcs = {}
        for prefix in prefixes:
            batch = [e for e in self.procs if e[0].startswith(prefix)]
            for name, p, _path in batch:
                self.stopped.add(name)
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            for name, p, _path in batch:
                try:
                    rcs[name] = p.wait(timeout=240)
                except subprocess.TimeoutExpired:
                    p.kill()
                    rcs[name] = f"killed after 240s ({p.wait()})"
        return rcs

    def kill_all(self):
        for _name, p, _path in self.procs:
            if p.poll() is None:
                p.kill()
                p.wait()


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            return "".join(f.readlines()[-n:])
    except OSError as e:
        return f"<{e}>"


class TeeStore:
    """What seed() writes goes to the store; the keys of the jobs whose
    one rule pins them to one node are noted, by node, on the way
    through."""

    _PIN = re.compile(r'"nids":\["([^"]+)"\]')

    def __init__(self, store):
        self.store = store
        self.pinned = {}                 # node id -> [job key]

    def put_many(self, items):
        for k, v in items:
            m = self._PIN.search(v)
            if m:
                self.pinned.setdefault(m.group(1), []).append(k)
        return self.store.put_many(items)


def pick_live_nodes(store, ks, n_nodes: int, n_agents: int) -> list:
    """The ids the real agents run under: spread over the fleet, taken
    from the nodes that belong to the fewest groups (at 10,240 nodes
    about half belong to none).  A group member also runs every
    group-placed Common job of its groups — thousands of executions
    this script does not judge, queued in front of the pinned ones it
    does; on the chip machine's shared cores that alone kept the
    judged executions minutes behind."""
    member_of = {f"bn{i:05d}": 0 for i in range(n_nodes)}
    for kv in store.get_prefix(ks.group):
        for nid in json.loads(kv.value)["nids"]:
            member_of[nid] += 1
    ranked = sorted(member_of, key=lambda n: (member_of[n], n))
    fewest = sum(c == member_of[ranked[0]] for c in member_of.values())
    pool = ranked[:max(fewest, n_agents)]
    return [pool[i * len(pool) // n_agents] for i in range(n_agents)]


def scalar_reference(store, ks, job_keys, s0: int, s1: int):
    """What the store holds for ``job_keys`` (single-rule, single-node
    jobs) and what the scalar engine says is due in [s0, s1): returns
    ({job id: (kind, node)}, {kind: {(job id, second)}})."""
    jobs, timers, phase_keys, anchors = {}, {}, {}, {}
    for key, kv in zip(job_keys, store.get_many(job_keys)):
        if kv is None:
            raise SmokeFailure(f"seeded job {key} is not in the store")
        doc = json.loads(kv.value)
        (rule,) = doc["rules"]
        jid = key.rsplit("/", 1)[1]
        jobs[jid] = (doc["kind"], rule["nids"][0])
        timers[jid] = rule["timer"]
        if rule["timer"].startswith("@every"):
            phase_keys[jid] = ks.phase_key("bench", jid, rule["id"])
    for jid, kv in zip(phase_keys,
                       store.get_many(list(phase_keys.values()))):
        timer, _, anchor = kv.value.rpartition("|")
        if timer != timers[jid]:
            raise SmokeFailure(f"phase anchor of {jid} is for {timer!r}, "
                               f"job has {timers[jid]!r}")
        anchors[jid] = int(anchor)
    due = {k: set() for k in (0, 1, 2)}
    for jid, (kind, _node) in jobs.items():
        due[kind] |= {(jid, s) for s in due_seconds(
            timers[jid], anchors.get(jid, 0), s0, s1)}
    return jobs, due


def cache_entries() -> int:
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(REPO, ".jax_cache")
    try:
        return sum(1 for n in os.listdir(d) if not n.endswith("-atime"))
    except OSError:
        return 0


def run_fleet(args) -> dict:
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from bench_sched import seed
    from cronsun_tpu.core import Keyspace
    from cronsun_tpu.logsink import RemoteJobLogStore
    from cronsun_tpu.store import RemoteStore

    failures = []
    ph = Phases()
    ks = Keyspace()
    fleet = Fleet()
    work = tempfile.mkdtemp(prefix="chip_smoke-")
    store = sink = None
    try:
        # ---- store + result store -----------------------------------
        # --native: bin.store compiles native/stored.cc from the
        # committed source when no current binary is there (about 5 s)
        # and serves it.  The Python server cannot carry this
        # deployment: with it, one 4 s window took 17 s to publish and
        # the agents' RPCs timed out (PERF.md, PR 22).
        store_p, store_log = fleet.spawn("store", "cronsun_tpu.bin.store",
                                         "--native", "--port", "0")
        logd_p, logd_log = fleet.spawn(
            "logd", "cronsun_tpu.bin.logd", "--port", "0",
            "--db", os.path.join(work, "logd.db"))
        store_addr = fleet.await_ready(store_p, store_log, 180)
        logd_addr = fleet.await_ready(logd_p, logd_log, 60)
        host, _, port = store_addr.rpartition(":")
        store = RemoteStore(host, int(port), timeout=600)
        lhost, _, lport = logd_addr.rpartition(":")
        sink = RemoteJobLogStore(lhost, int(lport), timeout=120)
        ph.done("servers", f"store {store_addr} (native: stored.cc, "
                           f"built from source by bin.store --native), "
                           f"logd {logd_addr} (python)")

        # ---- seed: every node but the live ones a placeholder --------
        tee = TeeStore(store)
        # the seeded command prints the second the execution was
        # scheduled FOR into its record: the record plane then carries
        # (job, scheduled second) for every kind, Common included
        seed(tee, ks, args.jobs, args.nodes, on_log=say, seed=args.seed,
             command="printenv CRONSUN_SCHEDULED_TS")
        # seed() registers every node as the placeholder "bench:1", and
        # a real agent refuses an id another host name holds
        # (node/agent.py _probe_duplicate): the live ids are freed
        live = pick_live_nodes(store, ks, args.nodes, args.agents)
        for nid in live:
            store.delete(ks.node_key(nid))
        pinned_keys = [k for nid in live for k in tee.pinned.get(nid, ())]
        n_cmd = store.count_prefix(ks.cmd)
        n_node = store.count_prefix(ks.node)
        if n_cmd != args.jobs or n_node != args.nodes - args.agents:
            failures.append(f"seeded {n_cmd} jobs / {n_node} placeholder "
                            f"nodes, wanted {args.jobs} / "
                            f"{args.nodes - args.agents}")
        ph.done("seed", f"{n_cmd} jobs, {n_node} placeholder nodes, "
                        f"{len(pinned_keys)} jobs pinned to the "
                        f"{len(live)} live ids (seed {args.seed})")

        # ---- conf + agents (registered BEFORE the scheduler loads) ---
        conf = os.path.join(work, "conf.json")
        with open(conf, "w") as f:
            # every TTL is the deployment's default (node_ttl 10 s)
            json.dump({"job_capacity": args.jobs,
                       "node_capacity": args.nodes,
                       "window_s": args.window_s,
                       "log_addr": logd_addr,
                       "log_db": os.path.join(work, "unused.db")}, f)
        agents = [fleet.spawn(f"node-{nid}", "cronsun_tpu.bin.node",
                              "--store", store_addr, "--conf", conf,
                              "--node-id", nid) for nid in live]
        for p, path in agents:
            fleet.await_ready(p, path, 120)
        registered = {nid: store.get(ks.node_key(nid)).mod_rev
                      for nid in live}
        ph.done("agents", f"{len(agents)} bin.node agents READY as "
                          + ",".join(live))

        # ---- the scheduler: the one process on the chip --------------
        cache_before = cache_entries()
        sched_p, sched_log = fleet.spawn(
            "sched", "cronsun_tpu.bin.sched", "--store", store_addr,
            "--conf", conf, "--node-id", SCHED_ID)
        fleet.await_ready(sched_p, sched_log, 900)
        with open(sched_log, errors="replace") as f:
            m = re.search(r"device (\{.*\})", f.read())
        if not m:
            raise SmokeFailure("bin.sched logged no device line before "
                               "READY:\n" + tail(sched_log))
        dev = json.loads(m.group(1))
        ph.done("sched cold load", f"import + connect + load of all rows "
                                   f"to READY; device {dev}")

        def snapshot():
            kv = store.get(ks.metrics_key("sched", SCHED_ID))
            return json.loads(kv.value) if kv is not None else None

        def wait_snapshot(pred, timeout, what):
            deadline = time.time() + timeout
            while time.time() < deadline:
                fleet.check_alive()
                snap = snapshot()
                if snap is not None and pred(snap):
                    return snap
                time.sleep(0.5)
            raise SmokeFailure(f"timed out after {timeout:.0f}s waiting "
                               f"for {what}; last snapshot {snapshot()}\n"
                               + tail(sched_log))

        # the first snapshot is published at the end of the first step:
        # READY -> here is the first window's compile (or cache load),
        # its run, and the hand-off of its orders
        first = wait_snapshot(
            lambda s: s["is_leader"] and s["steps_total"] >= 1, 600,
            "the scheduler to lead and finish its first step")
        ph.done("first step", f"leader, jobs loaded {first['jobs']}; "
                f"compile cache: {cache_entries() - cache_before} new "
                f"entries since the scheduler started, {cache_before} "
                f"were there (a cold first window compiles for ~30 s, "
                f"a cached one loads in under a second)")
        if first["jobs"] != args.jobs:
            failures.append(f"scheduler loaded {first['jobs']} jobs, "
                            f"wanted {args.jobs}")

        # ---- 5 consecutive windows, from the first unpublished second
        s0 = wait_snapshot(lambda s: s["published_through"] > 0, 300,
                           "the first window to land in the store"
                           )["published_through"]
        s1 = s0 + WINDOWS * args.window_s
        final = wait_snapshot(
            lambda s: s["published_through"] >= s1,
            s1 - time.time() + 300, f"seconds [{s0}, {s1}) to publish")
        ph.done("serve", f"{WINDOWS} windows x {args.window_s}s = "
                f"scheduled seconds [{s0}, {s1}) planned and published "
                f"(steps {first['steps_total']} -> "
                f"{final['steps_total']})")
        # a scheduler that keeps real time publishes a window BEFORE
        # its first second: said here, judged by no limit yet
        last = final["published_through"] - 1
        say(f"scheduler lateness: second {last} was published "
            f"{time.time() - last:+.1f}s after it was due (negative = "
            f"ahead of real time)")

        # ---- the scheduler's own account, then its end ---------------
        # It is stopped HERE, before the agents are judged: on this
        # host's shared cores it already publishes behind real time
        # (every agent filters every Common fire of the fleet, ~10k
        # keys/s, through the one store), and every further window
        # only puts the agents further behind the seconds judged
        # below.  Its stop drains in-flight windows and overflow
        # replans first.
        say("sched metrics: " + json.dumps({k: final[k] for k in (
            "steps_total", "dispatches_total", "sched_step_p50_ms",
            "sched_step_p99_ms", "tick_p50_ms", "tick_p99_ms",
            "publish_window_ms", "overflow_late_fires_total",
            "pipeline_stalls_total", "lease_resigns_total",
            *LOSS_COUNTERS)}))
        say("sched step spans p50 ms: " + json.dumps({
            k[len("step_span_"):-len("_p50_ms")]: v
            for k, v in final.items()
            if k.startswith("step_span_") and k.endswith("_p50_ms")}))
        if final["steps_total"] - first["steps_total"] < WINDOWS:
            failures.append(
                f"steps_total advanced {first['steps_total']} -> "
                f"{final['steps_total']}, fewer than {WINDOWS} windows")
        lost = {k: final[k] for k in LOSS_COUNTERS if final[k]}
        say(f"loss counters: {({k: final[k] for k in LOSS_COUNTERS})}")
        if lost:
            failures.append(f"loss counters not 0: {lost}")
        on_jax = [name for name, p, _ in fleet.procs
                  if fleet.imports_jax(p)]
        say(f"processes with jaxlib mapped: {on_jax}")
        if on_jax != ["sched"]:
            failures.append(f"JAX loaded in {on_jax}, wanted only sched")
        rcs = fleet.stop("sched")
        with open(sched_log, errors="replace") as f:
            text = f.read()
        for needle in ("scheduler step failed", "Traceback (most recent"):
            if needle in text:
                failures.append(f"sched log holds {needle!r}:\n"
                                + tail(sched_log))
        ph.done("sched stop", f"exit code {rcs['sched']}")

        # ---- the scalar reference for the pinned single-node jobs ----
        jobs, due = scalar_reference(store, ks, pinned_keys, s0, s1)
        must = due[0] | due[2]
        say(f"reference: {len(jobs)} pinned jobs, due pairs in window: "
            f"kind0 {len(due[0])}, kind1 {len(due[1])}, "
            f"kind2 {len(due[2])}")
        if not must:
            failures.append("the reference holds no due pair to check")

        # ---- consumption: records land through logd -------------------
        cursors = {nid: 0 for nid in live}
        executed = {}                    # (jid, second) -> count
        other = [0]                      # executions outside the check
        fences = {}                      # (jid, second) -> holder node

        def pull_records():
            # fences ride a shared lease the agent rotates (they live
            # lock_ttl/2 .. lock_ttl + a grace): gathered on every
            # pass, so one that expires while slower records are still
            # awaited is not missed
            for kv in store.get_prefix(ks.lock):
                jid, _, sec = kv.key[len(ks.lock):].rpartition("/")
                if jid in jobs and sec.isdigit() and s0 <= int(sec) < s1:
                    fences[(jid, int(sec))] = kv.value.partition("@")[0]
            for nid in live:
                while True:
                    recs, _ = sink.query_logs(
                        node=nid, after_id=cursors[nid], page_size=500)
                    for r in recs:
                        cursors[nid] = max(cursors[nid], r.id)
                        j = jobs.get(r.job_id)
                        sec = r.output.strip()
                        if j is None or j[1] != nid or not sec.isdigit() \
                                or not s0 <= int(sec) < s1:
                            other[0] += 1
                            continue
                        pair = (r.job_id, int(sec))
                        executed[pair] = executed.get(pair, 0) + 1
                        if not r.success:
                            failures.append(f"execution {pair} on {nid} "
                                            f"failed: {r.output!r}")
                    if len(recs) < 500:
                        break

        deadline = time.time() + 300
        while time.time() < deadline:
            fleet.check_alive()
            pull_records()
            if must <= executed.keys():
                break
            time.sleep(1.0)
        time.sleep(3.0)                  # a repeat would land by now
        pull_records()
        ph.done("consume", f"{sum(executed.values())} checked executions "
                f"recorded ({other[0]} others: group-placed jobs and "
                f"seconds outside the window)")

        # ---- executed == due ------------------------------------------
        by_kind = {k: {p for p in executed if jobs[p[0]][0] == k}
                   for k in (0, 1, 2)}
        twice = sorted(p for p, n in executed.items() if n > 1)
        for k in (0, 2):
            missing, extra = due[k] - by_kind[k], by_kind[k] - due[k]
            say(f"kind {k}: due {len(due[k])} executed "
                f"{len(by_kind[k])} missing {len(missing)} "
                f"not-due {len(extra)}")
            if missing or extra:
                failures.append(
                    f"kind {k}: missing {sorted(missing)[:5]} "
                    f"not-due {sorted(extra)[:5]}")
        extra = by_kind[1] - due[1]
        say(f"kind 1 (Alone): due {len(due[1])} executed "
            f"{len(by_kind[1])} not-due {len(extra)} (a live previous "
            f"run may rightly skip one)")
        if extra:
            failures.append(f"kind 1: not-due {sorted(extra)[:5]}")
        say(f"executed twice: {len(twice)}")
        if twice:
            failures.append(f"executed twice: {twice[:5]}")
        # the fence plane agrees for the exclusive kinds: one
        # create-if-absent fence per executed (job, second), held by
        # the pinned node
        excl = by_kind[1] | by_kind[2]
        wrong = [(p, n) for p, n in fences.items() if n != jobs[p[0]][1]]
        say(f"fence plane: {len(fences)} fences for "
            f"{len(excl)} exclusive executions, {len(wrong)} on a "
            f"node other than the pinned one")
        if set(fences) != excl or wrong:
            failures.append(
                f"fences != exclusive executions: only fenced "
                f"{sorted(set(fences) - excl)[:5]}, only executed "
                f"{sorted(excl - set(fences))[:5]}, wrong node "
                f"{wrong[:5]}")

        # a node whose lease lapses reads as down until it registers
        # again, and the exclusive fires pinned to it are dropped
        # meanwhile: a lapse under the deployment's own node_ttl is a
        # failure whether or not a judged fire fell into it
        again = [nid for nid in live
                 if (kv := store.get(ks.node_key(nid))) is None
                 or kv.mod_rev != registered[nid]]
        say(f"agents that had to register again (node lease lapsed): "
            f"{again}")
        if again:
            failures.append(f"node leases lapsed under load: {again}")
            for nid in again:
                failures.append(f"node-{nid}.log:\n"
                                + tail(os.path.join(OUT_DIR,
                                                    f"node-{nid}.log"), 6))
        # the store's own account of the run: the ops that cost most
        ops = sorted(store.op_stats().items(),
                     key=lambda kv: -kv[1].get("total_ms", 0))[:8]
        say("store op_stats (count, total ms, max ms): " + ", ".join(
            f"{op} {v['count']}/{v['total_ms']:.0f}/{v['max_ms']:.0f}"
            for op, v in ops))
        for nid in live:
            kv = store.get(ks.metrics_key("node", nid))
            if kv is not None:
                snap = json.loads(kv.value)
                say(f"agent {nid}: " + json.dumps({
                    k: snap.get(k) for k in (
                        "execs_total", "execs_failed_total",
                        "orders_consumed_total", "watch_losses_total",
                        "rec_dropped_total",
                        "exec_start_lag_p50_s", "exec_start_lag_p99_s")}))

        # ---- a clean end ------------------------------------------------
        sink.close()
        store.close()
        sink = store = None
        rcs.update(fleet.stop("node", "logd", "store"))
        bad = {n: rc for n, rc in rcs.items() if rc != 0}
        if bad:
            failures.append(f"children did not exit 0 on SIGTERM: {bad}")
        ph.done("stop", f"{len(rcs)} children, exit codes "
                        f"{sorted(set(map(str, rcs.values())))}")
    finally:
        for c in (sink, store):
            if c is not None:
                try:
                    c.close()
                except Exception:  # noqa: BLE001 — the server may be gone
                    pass
        fleet.kill_all()
        shutil.rmtree(work, ignore_errors=True)
    if "jax" in sys.modules:
        failures.append("this process imported jax")
    if failures:
        raise SmokeFailure("\n".join(failures))
    return dev


# ---------------------------------------------------------------------------
# four chips: the mesh planners only
# ---------------------------------------------------------------------------

def elig_words(rows, words, seed: int):
    """uint32 eligibility word for (row, word index): a multiply-xorshift
    mix that numpy and jax.numpy evaluate bit for bit on uint32 arrays,
    so the device builds its shard in place and the host can judge any
    placement without ever holding the matrix.  ~50 % of nodes per job."""
    import numpy as np
    u = np.uint32
    h = rows * u(0x9E3779B1) ^ (words + u(seed & 0xFFFF)) * u(0x85EBCA77)
    h = h ^ (h >> u(15))
    h = h * u(0x2C1B3C6D)
    h = h ^ (h >> u(12))
    h = h * u(0x297A2D39)
    return h ^ (h >> u(15))


def run_mesh4(args) -> dict:
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    from cronsun_tpu.bin.common import enable_compile_cache
    from cronsun_tpu.ops.schedule_table import (FRAMEWORK_EPOCH,
                                                ScheduleTable, build_table)
    from cronsun_tpu.parallel.mesh import (Sharded2DTickPlanner,
                                           ShardedTickPlanner, make_mesh,
                                           make_mesh2d)

    enable_compile_cache()
    failures = []
    ph = Phases()
    devs = jax.devices()
    dev = {"platform": devs[0].platform,
           "device_kind": devs[0].device_kind, "count": len(devs)}
    say(f"device {dev}")
    if len(devs) < 4:
        raise SmokeFailure(f"--mesh4 needs 4 devices, JAX reports "
                           f"{len(devs)}")
    n_jobs, n_nodes, W = args.jobs, args.nodes, args.window_s
    # second 17 of a minute, so the window [:17, :21) holds no herd
    # second: at :00 every */k job fires at once (~200k rows), past the
    # mesh planners' fixed 65,536 bucket, and each layout would keep a
    # different head of it — nothing to compare row for row
    epoch = 1_790_000_000 // 60 * 60 + 17
    say(f"cut: the window starts at second :{epoch % 60:02d}; herd "
        f"seconds (every */k job at :00) are not planned on the mesh "
        f"here — the one-chip fleet smoke meets them")

    # ---- host: the schedule table from seeded specs -----------------
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    from bench_sched import schedule_mix
    timers, anchors, kinds = schedule_mix(
        np.random.default_rng(args.seed), n_jobs, epoch - 1)
    exclusive_n = kinds != 0
    distinct, inverse = np.unique(timers, return_inverse=True)
    small = build_table(list(distinct), capacity=len(distinct) + 1)
    J = 1 << max(0, (n_jobs - 1).bit_length())
    J = max(J, 4 * 256)
    idx = np.full(J, len(distinct), np.int64)       # pad -> inactive row
    idx[:n_jobs] = inverse
    cols = {f: np.asarray(getattr(small, f))[idx]
            for f in ScheduleTable.__dataclass_fields__}
    ev = cols["is_every"]
    anchors_j = np.zeros(J, np.int64)
    anchors_j[:n_jobs] = anchors
    cols["phase_mod"] = np.where(
        ev, (anchors_j - FRAMEWORK_EPOCH) % cols["period"], 0
    ).astype(np.int32)
    table = ScheduleTable(**{k: jnp.asarray(v) for k, v in cols.items()})
    exclusive = np.zeros(J, bool)
    exclusive[:n_jobs] = exclusive_n
    cost = np.ones(J, np.float32)
    # exclusive slots per node: even nodes ration (the bound is met and
    # must hold), odd nodes take the deployment default (everything else
    # is placed, so eligibility is judged on tens of thousands of rows)
    node_caps = np.where(np.arange(n_nodes) % 2, 1 << 20, 4).astype(np.int32)
    table_mb = sum(v.nbytes for v in cols.values()) / 1e6
    ph.done("table", f"{n_jobs} rows in a {J}-row table from "
                     f"{len(distinct)} distinct specs, {table_mb:.0f} MB "
                     f"(seed {args.seed})")

    def in_use():
        out = []
        for d in devs[:4]:
            st = d.memory_stats() or {}
            out.append((st.get("bytes_in_use"),
                        st.get("peak_bytes_in_use")))
        return out

    def drive(name, planner):
        w32 = planner.N // 32
        planner.set_table(table)
        planner.set_job_meta_full(exclusive, cost)
        caps = np.zeros(planner.N, np.int32)     # pad columns stay shut
        caps[:n_nodes] = node_caps
        planner.set_node_capacity_full(caps)

        def gen():
            rows = jax.lax.broadcasted_iota(jnp.uint32, (planner.J, w32), 0)
            words = jax.lax.broadcasted_iota(jnp.uint32, (planner.J, w32), 1)
            return elig_words(rows, words, args.seed)
        planner.set_eligibility(
            jax.jit(gen, out_shardings=planner._shard2)())
        jax.block_until_ready(planner.elig)
        shards = sorted((s.device.id, s.data.nbytes)
                        for s in planner.elig.addressable_shards)
        total = planner.J * w32 * 4
        say(f"{name}: eligibility {total / 1e9:.2f} GB generated on "
            f"device, per-device shard bytes {shards}; per-device "
            f"(bytes_in_use, peak) {in_use()}")
        if len(shards) != 4 or any(b != total // 4 for _d, b in shards):
            failures.append(f"{name}: eligibility not spread 1/4 per "
                            f"device: {shards}")
        for used, _peak in in_use():
            # a quarter of the matrix, a quarter of the table, the
            # replicated node vectors — and nothing like a second copy
            if used is not None and used > total // 4 + (512 << 20):
                failures.append(f"{name}: a device holds {used} bytes, "
                                f"more than 1/4 of the matrix + 512 MiB")
        ph.done(f"{name} load", "")
        plans = planner.plan_window(epoch, W)
        ph.done(f"{name} plan (compile + run)",
                f"fired per second {[len(p.fired) for p in plans]}, "
                f"impl {planner.first_window_impl()}")
        return plans

    def judge(name, plans):
        """Placements: exclusive rows only, on an eligible node, within
        the node's capacity over the whole window."""
        per_node = np.zeros(n_nodes, np.int64)
        placed = skipped = 0
        for p in plans:
            if p.overflow:
                failures.append(f"{name}: second {p.epoch_s} overflowed "
                                f"the bucket by {p.overflow}")
            rows = p.fired.astype(np.int64)
            on = p.assigned >= 0
            if (exclusive[rows] != on).any():
                skipped += int((exclusive[rows] & ~on).sum())
                if (~exclusive[rows] & on).any():
                    failures.append(f"{name}: a Common row was placed")
            r = rows[on].astype(np.uint32)
            n = p.assigned[on].astype(np.int64)
            if (n >= n_nodes).any():
                failures.append(f"{name}: placement past node {n_nodes}")
                r, n = r[n < n_nodes], n[n < n_nodes]
            word = elig_words(r, (n // 32).astype(np.uint32), args.seed)
            ok = (word >> (n % 32).astype(np.uint32)) & 1
            if not ok.all():
                failures.append(f"{name}: {int((ok == 0).sum())} "
                                f"placements on ineligible nodes")
            np.add.at(per_node, n, 1)
            placed += len(n)
        over = int((per_node > node_caps).sum())
        say(f"{name}: {placed} placements on eligible nodes, "
            f"{skipped} skipped for capacity, "
            f"{int((per_node[::2] == 4).sum())} rationed nodes full, "
            f"{over} nodes over capacity")
        if over:
            failures.append(f"{name}: {over} nodes took more placements "
                            f"than their capacity")
        if not placed:
            failures.append(f"{name}: nothing was placed")

    # ---- 2x2 (jobs x nodes), then free it, then 4 (jobs) -------------
    p2 = Sharded2DTickPlanner(make_mesh2d(2, 2), job_capacity=J,
                              node_capacity=n_nodes)
    plans2 = drive("2x2", p2)
    judge("2x2", plans2)
    del p2
    gc.collect()
    p1 = ShardedTickPlanner(make_mesh(4), job_capacity=J,
                            node_capacity=n_nodes)
    plans1 = drive("4", p1)
    judge("4", plans1)
    del p1

    # ---- the layouts agree, and the scalar engine agrees -------------
    for a, b in zip(plans2, plans1):
        if set(a.fired.tolist()) != set(b.fired.tolist()):
            failures.append(f"2x2 and 4 disagree on the fired rows of "
                            f"second {a.epoch_s}")
    sample = np.random.default_rng(args.seed + 1).choice(
        n_jobs, size=min(n_jobs, 4096), replace=False)
    fired = [set(p.fired.tolist()) for p in plans2]
    wrong = 0
    for row in sample.tolist():
        want = due_seconds(str(timers[row]), int(anchors[row]), epoch,
                           epoch + W)
        got = {epoch + w for w in range(W) if row in fired[w]}
        wrong += want != got
    say(f"layouts agree on {W} seconds; scalar engine vs device on "
        f"{len(sample)} sampled rows: {wrong} rows differ")
    if wrong:
        failures.append(f"{wrong} sampled rows differ from the scalar "
                        f"engine")
    ph.done("compare", "")
    if failures:
        raise SmokeFailure("\n".join(failures))
    return dev


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--mesh4", action="store_true",
                    help="the four-chip mesh phase and nothing else")
    ap.add_argument("--jobs", type=int, default=1_000_000,
                    help="CPU rehearsal only (default: the full size)")
    ap.add_argument("--nodes", type=int, default=None,
                    help="CPU rehearsal only (default 10,240; 102,400 "
                         "with --mesh4)")
    ap.add_argument("--agents", type=int, default=8,
                    help="CPU rehearsal only")
    args = ap.parse_args()
    if args.nodes is None:
        args.nodes = 102_400 if args.mesh4 else 10_240
    with open(os.path.join(REPO, "conf", "base.json.sample")) as f:
        args.window_s = int(json.load(f)["window_s"])
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    t0 = time.time()
    say(f"chip_smoke: {'mesh4' if args.mesh4 else 'fleet'} "
        f"{args.jobs} jobs x {args.nodes} nodes"
        + ("" if args.mesh4 else f" x {args.agents} live agents")
        + f", window_s {args.window_s}, seed {args.seed}")
    try:
        dev = run_mesh4(args) if args.mesh4 else run_fleet(args)
    except SmokeFailure as e:
        say(f"FAILED after {time.time() - t0:.1f}s:\n{e}")
        return 1
    say(f"all phases passed in {time.time() - t0:.1f}s")
    # the device check comes last so that a CPU rehearsal exercises
    # everything before it — and still cannot end in the contract line
    if dev["platform"] != "tpu" or \
            (not args.mesh4 and dev["impl"] not in ("mixed", "pallas")):
        say(f"FAILED: the planner did not run on a TPU: {dev}")
        return 1
    if args.mesh4 and dev["count"] != 4:
        say(f"FAILED: --mesh4 saw {dev['count']} devices, wanted 4")
        return 1
    say(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["count"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
