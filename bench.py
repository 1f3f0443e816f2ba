#!/usr/bin/env python
"""Benchmark ladder (BASELINE.md) — prints ONE JSON line on stdout.

Headline: tick+assign latency @ 1M jobs x 10k nodes on one chip, sustained
(pipelined) per-tick — the north-star metric from BASELINE.json (<100 ms p99).
``vs_baseline`` is target_ms / measured_p99 (>1.0 beats the target).

Detail for every ladder config goes to bench_detail.json and stderr.

Run from the repo root, on a machine with the chip: it refuses any other
backend.
"""

import json
import sys
import time

import numpy as np

TARGET_MS = 100.0


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def git_rev() -> str:
    import os
    import subprocess
    try:
        out = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"], capture_output=True,
            text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)))
        return out.stdout.strip() or "unknown"
    except Exception:  # noqa: BLE001 — not a git checkout
        return "unknown"


def utc_now() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def check_artifact_provenance(rev: str) -> None:
    """Loud STALE warnings when a committed artifact's git_rev doesn't
    match HEAD — the "artifact predates PRs 1-5" trap, made structural:
    every bench run stamps git_rev + UTC timestamp into
    bench_detail.json and every MULTICHIP sidecar, and every run checks
    the committed ones before anyone quotes a number from them."""
    import glob
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    arts = [os.path.join(here, "bench_detail.json")] + sorted(
        glob.glob(os.path.join(here, "MULTICHIP_*.json"))
        + glob.glob(os.path.join(here, "PUSH_*.json")))
    for path in arts:
        if not os.path.exists(path):
            continue
        name = os.path.basename(path)
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            log(f"STALE? {name}: unreadable ({e})")
            continue
        art_rev = art.get("git_rev")
        if art_rev is None:
            log(f"STALE: {name} carries no git_rev stamp — it predates "
                f"provenance stamping entirely; its numbers reflect "
                f"unknown code.  Re-run `python bench.py` on the chip "
                f"before quoting it.")
        elif art_rev != rev:
            log(f"STALE: {name} was generated at rev {art_rev}, HEAD is "
                f"{rev} — its numbers predate the current code.  Re-run "
                f"`python bench.py` before quoting it.")


def synth_table(J, fire_period_lo, fire_period_hi, seed=0):
    import jax.numpy as jnp
    from cronsun_tpu.ops.schedule_table import ScheduleTable
    rng = np.random.default_rng(seed)
    cols = dict(
        sec_lo=np.zeros(J, np.uint32), sec_hi=np.zeros(J, np.uint32),
        min_lo=np.zeros(J, np.uint32), min_hi=np.zeros(J, np.uint32),
        hour=np.zeros(J, np.uint32), dom=np.zeros(J, np.uint32),
        month=np.zeros(J, np.uint32), dow=np.zeros(J, np.uint32),
        dom_star=np.zeros(J, bool), dow_star=np.zeros(J, bool),
        is_every=np.ones(J, bool),
        period=rng.integers(fire_period_lo, fire_period_hi, J).astype(np.int32),
        active=np.ones(J, bool), paused=np.zeros(J, bool),
        has_dep=np.zeros(J, bool), dep_policy=np.zeros(J, np.int32),
        dep_cols=np.full((J, 8), -1, np.int32),
        tenant=np.zeros(J, np.int32),
        jitter=np.zeros(J, np.int32))
    # Uniform phases over each job's own period: steady aggregate fire rate
    # (clustered phases make bursty seconds that overflow the fired bucket).
    cols["phase_mod"] = (rng.integers(0, 1 << 30, J) % cols["period"]).astype(np.int32)
    return ScheduleTable(**{k: jnp.asarray(v) for k, v in cols.items()})


def bench_ticks(p, t0, n, pipeline=8, sla=None):
    """Sustained pipelined per-tick ms over n ticks (fixed SLA bucket so
    adaptive resizing never recompiles inside the timed region)."""
    handles = []
    start = time.time()
    for i in range(n):
        handles.append(p.plan_async(t0 + i, sla_bucket=sla))
        if len(handles) > pipeline:
            p.gather(handles.pop(0))
    for h in handles:
        p.gather(h)
    return (time.time() - start) / n * 1000


def bench_windows(p, t0, n_windows, W, pipeline=2, sla=None):
    """Sustained windowed per-tick ms: n_windows dispatches of W seconds."""
    handles = []
    start = time.time()
    for i in range(n_windows):
        handles.append(p.plan_window_async(t0 + i * W, W, sla_bucket=sla))
        if len(handles) > pipeline:
            p.gather_window(handles.pop(0))
    for h in handles:
        p.gather_window(h)
    return (time.time() - start) / (n_windows * W) * 1000


def window_intervals(p, t0, n_windows, W, pipeline=2, sla=None):
    """Steady-state per-tick ms as a DISTRIBUTION: pipelined windowed
    dispatches, timestamp each gather while the pipeline is still being
    fed (drain-phase gathers complete instantly and are excluded), and
    return the inter-completion intervals divided by W.  p99 over these
    is a real tail over windows — p99 over a handful of run MEANS (the
    old method) collapses to max-of-means and swings 2-3x on a single
    slow window (the 22.7 -> 60.8 ms mystery in docs/DESIGN.md)."""
    handles = []
    stamps = []
    for i in range(n_windows):
        handles.append(p.plan_window_async(t0 + i * W, W, sla_bucket=sla))
        if len(handles) > pipeline:
            p.gather_window(handles.pop(0))
            stamps.append(time.time())
    for h in handles:
        p.gather_window(h)
    return np.diff(stamps) / W * 1000


def bench_ticks_sync(p, t0, n, sla=None):
    lat = []
    for i in range(n):
        s = time.time()
        p.plan(t0 + i, sla_bucket=sla)
        lat.append((time.time() - s) * 1000)
    return np.array(lat)


def main():
    quick = "--quick" in sys.argv
    rev = git_rev()
    check_artifact_provenance(rev)
    detail = {"git_rev": rev, "generated_at_utc": utc_now()}
    # A chip belongs to one process at a time, and several legs below
    # start children that plan on it (scripts/bench_sched.py builds a
    # SchedulerService + TickPlanner).  So the order is: a probe child
    # that names the backend and exits, every subprocess leg, and only
    # then this process's own first JAX import for the device ladder.
    import os
    import subprocess
    probe = subprocess.run(
        [sys.executable, "-c",
         "import jax; print(jax.default_backend())"],
        capture_output=True, text=True, timeout=300)
    backend = (probe.stdout.split() or ["none"])[-1]
    if probe.returncode != 0 or backend != "tpu":
        log(f"bench.py measures the chip: backend is {backend!r}, not "
            f"'tpu' — refusing to record a headline.\n{probe.stderr[-500:]}")
        sys.exit(2)

    # ---- dispatch plane: plan -> put_many -> agent -> fence -> log ---------
    # The path the reference spends its time on (SURVEY §3.2: etcd round
    # trips + 4 Mongo writes per execution).  Runs as a subprocess sweep
    # against the native store with REAL agent processes; merged into the
    # same artifact so the system claim sits beside the kernel claim.
    log("dispatch plane: store+agents end-to-end sweep")
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        # past 40k offered/s in bundle mode: the per-agent bundle-mode
        # drain ceiling is read off the at/past-saturation rates
        rates = "500,1000" if quick else "2000,10000,40000,80000"
        sweep = "1" if quick else "1,2,4,8"
        proc = subprocess.run(
            [sys.executable, os.path.join(here, "scripts",
                                          "bench_dispatch.py"),
             "--rates", rates, "--seconds", "3", "--agent-sweep", sweep],
            capture_output=True, text=True, timeout=1800, cwd=here)
        if proc.returncode == 0:
            detail.update(json.loads(proc.stdout))
        else:
            detail["dispatch_plane_error"] = proc.stderr[-500:]
    except Exception as e:  # noqa: BLE001 — the TPU bench must still land
        detail["dispatch_plane_error"] = str(e)
    # the shard-count ladder: one past-saturation rate at a fixed
    # agent count across 1/2/4 store shards — the horizontal-scaling
    # claim (ORDER drain past the one-PROCESS store ceiling) measured
    # in the same artifact.  The store side is BENCH_STORE=py, one
    # bin.store process per shard: the GIL-bound backend is the one
    # whose single-process ceiling sits below the fleet's drive
    # capacity on one host, so its curve shows the partitioning win
    # (the native server is internally striped and multithreaded — its
    # shard win is per-machine).  Own error scope: a ladder failure
    # must not mislabel the (already merged) sweep as failed.
    if not quick:
        log("dispatch plane: store shard ladder 1/2/4")
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(here, "scripts",
                                              "bench_dispatch.py"),
                 "--rates", "150000", "--seconds", "3", "--agents", "8",
                 "--shard-ladder", "1,2,4"],
                capture_output=True, text=True, timeout=1800, cwd=here,
                env={**os.environ, "BENCH_STORE": "py"})
            if proc.returncode == 0:
                detail.update(json.loads(proc.stdout))
            else:
                detail["dispatch_plane_shard_ladder_error"] = \
                    proc.stderr[-500:]
        except Exception as e:  # noqa: BLE001
            detail["dispatch_plane_shard_ladder_error"] = str(e)
    # the RESULT-plane shard ladder: one past-ingest-ceiling rate at a
    # fixed agent count across 1/2/4 logd shards — the record-drain
    # scaling curve the sharded result plane must deliver (PR 6's probe
    # measured the unsharded logd as the wall at ~33k records/s).
    # BENCH_LOGD=py (one bin.logd process per shard) is the backend
    # whose single-process ceiling the sharding removes on one host —
    # the store-ladder lesson applied to logd.
    if not quick:
        log("result plane: logd shard ladder 1/2/4")
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(here, "scripts",
                                              "bench_dispatch.py"),
                 "--rates", "60000", "--seconds", "3", "--agents", "4",
                 "--logd-shards", "1,2,4"],
                capture_output=True, text=True, timeout=1800, cwd=here,
                env={**os.environ, "BENCH_LOGD": "py"})
            if proc.returncode == 0:
                detail.update(json.loads(proc.stdout))
            else:
                detail["result_plane_logd_ladder_error"] = \
                    proc.stderr[-500:]
        except Exception as e:  # noqa: BLE001
            detail["result_plane_logd_ladder_error"] = str(e)
    # the READ plane: queries/s + p50/p99 for the three dashboard
    # shapes (latest view, paged history filter, stat_days) at M
    # concurrent readers while a writer drives bulk ingest at full
    # drain — the query-path claim beside the ingest claim.  Runs in
    # quick mode too (it is cheap) so every artifact carries it.
    log("query plane: concurrent readers under full-drain writes")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(here, "scripts",
                                          "bench_query.py"),
             "--logd-shards", "1" if quick else "2",
             "--readers", "4" if quick else "6",
             "--seconds", "2" if quick else "4"]
            # full runs exercise the tier boundary: an aged-out day
            # behind the watermark, 20% of history reads crossing it
            + ([] if quick else ["--cold-fraction", "0.2"]),
            capture_output=True, text=True, timeout=600, cwd=here)
        if proc.returncode == 0:
            detail.update(json.loads(proc.stdout))
        else:
            detail["query_plane_error"] = proc.stderr[-500:]
    except Exception as e:  # noqa: BLE001
        detail["query_plane_error"] = str(e)
    # the PUSH plane: M concurrent SSE viewers on /v1/stream against
    # paced live ingest — publish-lag p50/p99, bytes-per-viewer, and
    # logd read ops vs the equivalent poll load at the same freshness
    # (the >= 10x claim).  Quick runs use a smaller fleet; full runs
    # drive the 1k-viewer gate.
    log("push plane: SSE fan-out vs poll at equal freshness")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(here, "scripts",
                                          "bench_push.py"),
             "--viewers", "150" if quick else "1000",
             "--seconds", "3" if quick else "8",
             "--write-rate", "50" if quick else "20"],
            capture_output=True, text=True, timeout=600, cwd=here)
        if proc.returncode == 0:
            detail.update(json.loads(proc.stdout))
        else:
            detail["push_plane_error"] = proc.stderr[-500:]
    except Exception as e:  # noqa: BLE001
        detail["push_plane_error"] = str(e)

    # ---- web-replica scale-out ladder --------------------------------------
    # N web replicas (subprocesses) share nothing but the logd
    # addresses; aggregate connected viewers should scale near-
    # linearly at equal lag — benched, not asserted.  Full runs also
    # refresh the PUSH_ladder.json sidecar (git_rev-stamped).
    log("push plane: web-replica scale-out ladder")
    try:
        cmd = [sys.executable, os.path.join(here, "scripts",
                                            "bench_push.py"),
               "--replicas", "1,2" if quick else "1,2,4",
               "--viewers", "100" if quick else "400",
               "--seconds", "3" if quick else "6",
               "--write-rate", "20"]
        if not quick:
            cmd += ["--out", os.path.join(here, "PUSH_ladder.json")]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=900, cwd=here)
        if proc.returncode == 0:
            merged = json.loads(proc.stdout)
            # the parent's provenance stamp wins over the child's
            merged.pop("git_rev", None)
            merged.pop("generated_at_utc", None)
            detail.update(merged)
        else:
            detail["push_ladder_error"] = proc.stderr[-500:]
    except Exception as e:  # noqa: BLE001
        detail["push_ladder_error"] = str(e)

    # ---- store snapshot write-stall probe ----------------------------------
    # the staggered-imaging claim: p99 client-visible put latency DURING
    # a snapshot, full-lock hold vs per-stripe COW imaging, both
    # backends (snapshot_write_stall_p99_ms_* / snapshot_stall_ratio_*).
    # Cheap enough for quick runs at a smaller keyspace.
    log("store: snapshot write-stall probe (full-lock vs staggered)")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(here, "scripts",
                                          "bench_store.py"),
             "--stall-probe",
             "--stall-keys", "50000" if quick else "200000"],
            capture_output=True, text=True, timeout=600, cwd=here)
        if proc.returncode == 0:
            detail.update(json.loads(proc.stdout))
        else:
            detail["snapshot_stall_probe_error"] = proc.stderr[-500:]
    except Exception as e:  # noqa: BLE001
        detail["snapshot_stall_probe_error"] = str(e)

    # ---- multichip mesh ladder ---------------------------------------------
    # tick+assign across device counts on the 1-D and 2-D meshes,
    # replicated-waterfill vs bucket-sharded bidding, with per-phase
    # breakdown and the per-round collective-bytes model (forced-host
    # CPU devices in subprocesses; BENCH_MESH_TPU=1 on a multi-chip
    # host uses real chips).  Full runs also refresh the
    # MULTICHIP_ladder.json sidecar (git_rev-stamped).
    log("multichip: mesh latency ladder")
    try:
        cmd = [sys.executable,
               os.path.join(here, "scripts", "bench_mesh.py")]
        if quick:
            cmd.append("--quick")
        else:
            cmd += ["--devices", "1,2,4,8", "--shapes", "65536x1024",
                    "--out", os.path.join(here, "MULTICHIP_ladder.json")]
        # outer budget >= worst-case sum of per-worker budgets (the
        # full ladder is up to 12 workers x 600 s each, plus the six
        # sparse-tick rungs at 900 s each; merged keys now include
        # multichip_sparse_ladder / multichip_demand_format /
        # multichip_divergence_*)
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=12660, cwd=here)
        if proc.returncode == 0:
            merged = json.loads(proc.stdout)
            # the parent's provenance stamp wins over the child's
            merged.pop("git_rev", None)
            merged.pop("generated_at_utc", None)
            detail.update(merged)
        else:
            detail["multichip_ladder_error"] = proc.stderr[-500:]
    except Exception as e:  # noqa: BLE001
        detail["multichip_ladder_error"] = str(e)

    # ---- scheduler system: full step() + failover at c5 scale --------------
    # The whole cycle a real tick pays (watch drain + reconcile + flush +
    # plan + order build + bulk publish) against the native store, plus
    # the failover story: cold load vs warm-standby takeover (VERDICT r3
    # #3/#4) vs checkpoint-restore warm takeover (failover_warm_* /
    # sched_checkpoint_* keys, merged below like the rest).  Full runs
    # only — at 1M jobs this is minutes.
    if not quick:
        log("scheduler system: full step + failover @ 1M jobs")
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(here, "scripts",
                                              "bench_sched.py"),
                 "--jobs", "1000000", "--nodes", "10240", "--steps", "30"],
                capture_output=True, text=True, timeout=3600, cwd=here)
            if proc.returncode == 0:
                detail.update(json.loads(proc.stdout))
            else:
                detail["sched_bench_error"] = proc.stderr[-500:]
        except Exception as e:  # noqa: BLE001
            detail["sched_bench_error"] = str(e)

    # ---- partitioned scheduler plane: the P-leader ladder ------------------
    # The same job set planned by 1/2/4 independent partition leaders
    # (ISSUE 15): aggregate planned-fire throughput over the slowest
    # partition's busy time, per-partition step p99, FNV-split
    # fairness, and zero fire-set divergence vs the P=1 scheduler
    # (sched_partition_* keys).
    if not quick:
        log("partitioned scheduler plane: ladder 1,2,4 @ 200k jobs")
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(here, "scripts",
                                              "bench_sched.py"),
                 "--partition-ladder", "1,2,4", "--jobs", "200000",
                 "--nodes", "1024", "--steps", "6"],
                capture_output=True, text=True, timeout=3600, cwd=here)
            if proc.returncode == 0:
                detail.update(json.loads(proc.stdout))
            else:
                detail["partition_ladder_error"] = proc.stderr[-500:]
        except Exception as e:  # noqa: BLE001
            detail["partition_ladder_error"] = str(e)

    # ---- workflow DAG plane: chain latency + exactly-once @ 50k ------------
    # Dependency-triggered jobs evaluated in the batched tick: a 3-stage
    # fan-out/fan-in DAG at 50k jobs x 512 nodes, chain-latency p50/p99
    # (upstream-success -> downstream-fire), exactly-once fire counts,
    # and a warm takeover with zero dispatch divergence (dag_* keys).
    if not quick:
        log("workflow DAG plane: chain latency @ 50k jobs x 512 nodes")
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(here, "scripts",
                                              "bench_sched.py"),
                 "--dag", "--jobs", "50000", "--nodes", "512",
                 "--rounds", "3"],
                capture_output=True, text=True, timeout=3600, cwd=here)
            if proc.returncode == 0:
                detail.update(json.loads(proc.stdout))
            else:
                detail["dag_bench_error"] = proc.stderr[-500:]
        except Exception as e:  # noqa: BLE001
            detail["dag_bench_error"] = str(e)

    # ---- trace plane: per-stage lag + sampling overhead @ 50k --------------
    # Fire-lifecycle tracing at 50k jobs x 512 nodes: a live mini-fleet
    # answers "which stage owns fire latency" from the trace plane
    # itself (trace_stage_p99_ms, one key per waterfall stage), and a
    # paired-interleave gate pins head-sampling's scheduler cost at
    # < 2% step p99 vs CRONSUN_TRACE=off (trace_overhead_* keys).
    if not quick:
        log("trace plane: stage breakdown + overhead @ 50k x 512")
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(here, "scripts",
                                              "bench_sched.py"),
                 "--trace", "--jobs", "50000", "--nodes", "512",
                 "--seconds", "8"],
                capture_output=True, text=True, timeout=1800, cwd=here)
            if proc.returncode == 0:
                detail.update(json.loads(proc.stdout))
            else:
                detail["trace_bench_error"] = proc.stderr[-500:]
        except Exception as e:  # noqa: BLE001
            detail["trace_bench_error"] = str(e)

    # ---- herd smearing: minute-boundary p99 A/B @ 50k ----------------------
    # Deterministic per-job jitter (ISSUE 19): the same minute-boundary
    # herd with jitter 0 vs 30 s — the smeared arm's herd-second
    # build+publish p99 must improve >= 2x with the fire set exactly
    # matching the pure-Python reference (herd_* / herd_smear_* keys).
    if not quick:
        log("herd smearing: minute-boundary A/B @ 50k x 512")
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(here, "scripts",
                                              "bench_sched.py"),
                 "--herd", "--jobs", "50000", "--nodes", "512",
                 "--jitter", "30"],
                capture_output=True, text=True, timeout=1800, cwd=here)
            if proc.returncode == 0:
                detail.update(json.loads(proc.stdout))
            else:
                detail["herd_bench_error"] = proc.stderr[-500:]
        except Exception as e:  # noqa: BLE001
            detail["herd_bench_error"] = str(e)

    # ---- multi-tenant admission: skewed-tenant workload --------------------
    # Zipf victim tenants + one noisy tenant offering 10x its fire-rate
    # quota: the noisy tenant must clamp to its quota (±5%) with loud
    # throttle counters while the victims stay exactly-once with fire-
    # latency p99 within 1.5x of the no-noisy-neighbor baseline
    # (tenant_* keys).
    if not quick:
        log("multi-tenant admission: skewed-tenant workload")
        try:
            proc = subprocess.run(
                [sys.executable, os.path.join(here, "scripts",
                                              "bench_sched.py"),
                 "--tenants", "--victim-jobs", "2000",
                 "--noisy-rate", "100", "--seconds", "60"],
                capture_output=True, text=True, timeout=1800, cwd=here)
            if proc.returncode == 0:
                detail.update(json.loads(proc.stdout))
            else:
                detail["tenant_bench_error"] = proc.stderr[-500:]
        except Exception as e:  # noqa: BLE001
            detail["tenant_bench_error"] = str(e)

    # ---- device ladder: this process takes the chip from here on ----------
    import jax
    import jax.numpy as jnp
    from cronsun_tpu.cron.parser import parse
    from cronsun_tpu.ops.planner import TickPlanner
    from cronsun_tpu.ops.schedule_table import build_table
    from cronsun_tpu.ops.tick import next_fire
    detail["backend"] = jax.default_backend()
    detail["device"] = str(jax.devices()[0])
    T0 = 1_753_000_000
    rng = np.random.default_rng(0)

    # Host<->device round-trip floor: the minimum any SYNCHRONOUS per-call
    # metric can reach on this host (dispatch + 4-byte fetch of a trivial
    # op); not measured on a locally attached chip.
    x = jnp.zeros(1, jnp.int32)
    np.asarray(x + 1)
    rtts = []
    for _ in range(40):
        s = time.time()
        np.asarray(x + 1)
        rtts.append((time.time() - s) * 1000)
    detail["rtt_floor_ms"] = round(float(np.median(rtts)), 2)
    # the floor's own tail: any sync_p99 below rtt_p99 is attributable
    # to dispatch jitter, not device compute (docs/DESIGN.md "sync-tick
    # latency attribution")
    detail["rtt_p90_ms"] = round(float(np.percentile(rtts, 90)), 2)
    detail["rtt_p99_ms"] = round(float(np.percentile(rtts, 99)), 2)

    # On-TPU kernel equivalence: compiled pallas bid/fanout vs the jnp
    # reference path, at collision scale (dense ties across 10k nodes)
    # and — full runs only — at the wide scale that exercises the
    # node-tiled kernel paths incl. cross-tile tie merging on REAL
    # hardware, not just the CPU interpreter tests.
    from cronsun_tpu.ops.assign import _bid_jnp, _fanout_jnp
    from cronsun_tpu.ops.pallas_kernels import bid_argmin, fanout_add
    Keq = 2048
    w_eq = jnp.asarray(rng.random(Keq).astype(np.float32))
    eq_scales = [("", 10240)] + ([] if quick else [("_wide", 102400)])
    for suffix, n_eq in eq_scales:
        packed_eq = jax.random.bits(jax.random.PRNGKey(7), (Keq, n_eq // 32),
                                    dtype=jnp.uint32)
        # heavy ties: loads quantized to 4 distinct values
        load_eq = jnp.asarray(rng.integers(0, 4, n_eq).astype(np.float32))
        bp, cp = bid_argmin(packed_eq, load_eq)
        bj, cj = _bid_jnp(packed_eq, load_eq)
        fp = fanout_add(packed_eq, w_eq)
        fj = _fanout_jnp(packed_eq, w_eq)
        detail[f"kernels_equal{suffix}"] = (
            # bid choices must be BIT-identical (placement determinism);
            # fanout is an f32 sum whose MXU accumulation order differs
            # from einsum's — equality up to accumulation noise (~2e-4
            # relative at 2k terms, measured) is the correct bar for a
            # load estimate
            bool(jnp.array_equal(cp, cj))
            and bool(jnp.allclose(bp, bj, rtol=1e-6, atol=1e-6))
            and bool(jnp.allclose(fp, fj, rtol=1e-3, atol=1e-2)))
    kernels_equal = detail["kernels_equal"]
    log(f"kernels_equal={kernels_equal} "
        f"wide={detail.get('kernels_equal_wide', 'n/a')} "
        f"rtt_floor={detail['rtt_floor_ms']}ms")

    # Per-kernel device time, pallas vs jnp, net of the link, at BOTH
    # sides of the impl="auto" threshold (assign.choose_impl): time a
    # jit of n chained applications (inputs varied per iteration so XLA
    # cannot hoist the unpack) for two n and difference out the RTT.
    # Rounds interleave all measurements so chip/link drift cancels;
    # min-per-quantity is the right estimator for fixed compute +
    # one-sided noise.
    import functools

    def chained(fn, reduce_out):
        @functools.partial(jax.jit, static_argnums=(2,))
        def run(packed, aux, n):
            def body(i, acc):
                out = fn(packed ^ jnp.uint32(i), aux)
                return acc + reduce_out(out) * 1e-30
            return jax.lax.fori_loop(0, n, body, jnp.float32(0.0))
        return run

    NBIG = 201
    scales = [("", 10240)] + ([] if quick else [("_wide", 102400)])
    runners = {}
    for suffix, n_nodes in scales:
        kp = jax.random.bits(jax.random.PRNGKey(7), (2048, n_nodes // 32),
                             dtype=jnp.uint32)
        ld = jnp.asarray(rng.integers(0, 4, n_nodes).astype(np.float32))
        wt = jnp.asarray(rng.random(2048).astype(np.float32))
        for impl, bid_f, fan_f in (("pallas", bid_argmin, fanout_add),
                                   ("jnp", _bid_jnp, _fanout_jnp)):
            runners[f"bid{suffix}_{impl}"] = (
                chained(bid_f, lambda o: jnp.sum(o[0])), kp, ld)
            runners[f"fanout{suffix}_{impl}"] = (
                chained(fan_f, jnp.sum), kp, wt)
    for r, a, b in runners.values():                    # compile both n
        np.asarray(r(a, b, 1))
        np.asarray(r(a, b, NBIG))
    kbest = {(k, n): np.inf for k in runners for n in (1, NBIG)}
    for _ in range(3 if quick else 5):
        for k, (r, a, b) in runners.items():
            for n in (1, NBIG):
                s = time.time()
                np.asarray(r(a, b, n))
                kbest[(k, n)] = min(kbest[(k, n)], time.time() - s)
    for name in runners:
        detail[f"kernel_{name}_ms"] = round(
            max(0.0, kbest[(name, NBIG)] - kbest[(name, 1)])
            * 1000 / (NBIG - 1), 3)
    log("kernel ms/call: " + " ".join(
        f"{k}={detail[f'kernel_{k}_ms']}" for k in sorted(runners)))

    # ---- config 1: 100-job single-node tick --------------------------------
    log("config 1: 100-job single-node tick")
    p1 = TickPlanner(job_capacity=128, node_capacity=32, max_fire_bucket=128)
    specs = [parse(f"{i % 60} * * * * *") for i in range(60)] + \
            [parse("* * * * * *")] * 40
    p1.set_table(build_table(specs, capacity=p1.J))
    p1.elig = jnp.ones_like(p1.elig)
    p1.exclusive = jnp.ones(p1.J, bool)
    p1.set_node_capacity([0], [1 << 20])
    bench_ticks_sync(p1, T0, 3)  # warm
    lat1 = bench_ticks_sync(p1, T0 + 10, 10 if quick else 60)
    detail["c1_100job_tick_p50_ms"] = round(float(np.percentile(lat1, 50)), 2)
    detail["c1_100job_tick_p99_ms"] = round(float(np.percentile(lat1, 99)), 2)

    # ---- config 2: 10k mixed specs, batched next-fire ----------------------
    log("config 2: 10k mixed cron specs, batched next-fire")
    mixed = []
    for i in range(10_000):
        r = i % 5
        if r == 0:
            mixed.append(f"@every {rng.integers(1, 300)}s")
        elif r == 1:
            mixed.append(f"{rng.integers(0,60)} {rng.integers(0,60)} * * * *")
        elif r == 2:
            mixed.append(f"*/{rng.integers(2,30)} * * * * *")
        elif r == 3:
            mixed.append(f"0 {rng.integers(0,60)} {rng.integers(0,24)} * * "
                         f"{rng.integers(0,7)}")
        else:
            mixed.append(f"0 0 {rng.integers(0,24)} {rng.integers(1,29)} * ?")
    t2 = build_table([parse(s) for s in mixed], phase_epoch_s=T0)
    next_fire(t2, T0)  # warm/compile
    ts = []
    for i in range(3 if quick else 10):
        s = time.time()
        r = next_fire(t2, T0 + i * 37)
        ts.append((time.time() - s) * 1000)
    detail["c2_10k_nextfire_p50_ms"] = round(float(np.median(ts)), 2)
    detail["c2_10k_nextfire_resolved"] = int((r >= 0).sum())

    # ---- configs 3-5: eligibility + assignment ladder ----------------------
    def ladder(name, J, N, fire_rate, caps, bucket, ticks):
        log(f"{name}: {J} jobs x {N} nodes, fire~{fire_rate:.0%}")
        # split buckets: ~50% of synth jobs are exclusive, so each kind's
        # bucket needs half the combined SLA
        bucket = (max(2048, bucket // 2), max(2048, bucket // 2))
        p = TickPlanner(job_capacity=J, node_capacity=N,
                        max_fire_bucket=max(bucket))
        period_lo = max(2, int(1 / fire_rate * 0.7))
        period_hi = max(period_lo + 2, int(1 / fire_rate * 1.4))
        p.set_table(synth_table(p.J, period_lo, period_hi))
        p.elig = jax.random.bits(jax.random.PRNGKey(1), (p.J, p.N // 32),
                                 dtype=jnp.uint32)
        p.exclusive = jnp.asarray(rng.random(p.J) < 0.5)
        p.set_node_capacity(list(range(p.N)), [caps] * p.N)
        bench_ticks(p, T0, 3, sla=bucket)  # warm + compile
        sus = bench_ticks(p, T0 + 100, ticks, sla=bucket)
        lat = bench_ticks_sync(p, T0 + 1000, max(5, ticks // 2), sla=bucket)
        fired = p.gather(p.plan_async(T0 + 2000, sla_bucket=bucket)).fired
        return {f"{name}_sustained_ms": round(sus, 2),
                f"{name}_sync_p50_ms": round(float(np.percentile(lat, 50)), 2),
                f"{name}_sync_p99_ms": round(float(np.percentile(lat, 99)), 2),
                f"{name}_fired_per_tick": int(len(fired))}

    n_ticks = 6 if quick else 30
    detail.update(ladder("c3_10kx1k", 10_000, 1024, 0.5, 1 << 20, 8192,
                         n_ticks))
    detail.update(ladder("c4_100kx1k", 100_000, 1024, 0.2, 64, 32768,
                         n_ticks))
    # bucket (16384, 16384): fired ~20.8k/tick splits ~10.4k per kind —
    # 2x headroom per bucket at half the fetch bytes of the old 65536
    r5 = ladder("c5_1Mx10k", 1 << 20, 10240, 0.02, 1 << 20, 32768, n_ticks)
    detail.update(r5)

    # headline: windowed planning (the production cadence — plan W seconds
    # ahead in one dispatch; semantics identical to W sequential ticks).
    # p50/p99 are taken over per-window steady-state completion intervals
    # (see window_intervals) — a distribution over real windows, robust to
    # a single slow window yet still an honest tail.
    W = 8
    p = TickPlanner(job_capacity=1 << 20, node_capacity=10240,
                    max_fire_bucket=65536)
    p.set_table(synth_table(p.J, 35, 70))
    p.elig = jax.random.bits(jax.random.PRNGKey(2), (p.J, p.N // 32),
                             dtype=jnp.uint32)
    p.exclusive = jnp.asarray(rng.random(p.J) < 0.5)
    p.set_node_capacity(list(range(p.N)), [1 << 20] * p.N)
    log(f"headline: 1M x 10k windowed (W={W})")
    SLA = (16384, 16384)
    bench_windows(p, T0, 2, W, sla=SLA)  # warm + compile
    # n >= 100 window samples from >= 2 separated passes (VERDICT r4
    # #4): at n=50 the p99 was essentially the max and swung on a
    # single slow window; the per-pass p99s are recorded so the
    # artifact shows the intra-run spread too
    reps = 1 if quick else 2
    rep_intervals = [
        window_intervals(p, T0 + 10_000 * r, 12 if quick else 60, W,
                         sla=SLA)
        for r in range(reps)]
    per_win = np.concatenate(rep_intervals)
    detail["headline_rep_p99s_ms"] = [
        round(float(np.percentile(x, 99)), 2) for x in rep_intervals]
    headline_p50 = float(np.percentile(per_win, 50))
    headline_p99 = float(np.percentile(per_win, 99))
    fired = p.gather(p.plan_async(T0 + 50000, sla_bucket=SLA)).fired
    detail["headline_windowed_p50_ms_per_tick"] = round(headline_p50, 2)
    detail["headline_windowed_p99_ms_per_tick"] = round(headline_p99, 2)
    detail["headline_window_samples"] = int(len(per_win))
    detail["headline_window_s"] = W
    detail["headline_fired_per_tick"] = int(len(fired))
    detail["headline_jobs_per_sec_per_chip"] = int(
        len(fired) / (headline_p99 / 1000))
    # throughput-optimal cadence: W=32 amortizes the link RTT 4x further
    # at the cost of job updates taking effect up to 32 s later —
    # recorded as a secondary figure, not the headline, because the
    # deployment default keeps the shorter window
    if not quick:
        bench_windows(p, T0 + 80_000, 1, 32, sla=SLA)   # warm W=32
        w32 = window_intervals(p, T0 + 90_000, 52, 32, sla=SLA)
        detail["w32_windowed_p50_ms_per_tick"] = round(
            float(np.percentile(w32, 50)), 2)
        detail["w32_windowed_p99_ms_per_tick"] = round(
            float(np.percentile(w32, 99)), 2)
        detail["w32_window_samples"] = int(len(w32))

    with open("bench_detail.json", "w") as f:
        json.dump(detail, f, indent=1)
    log(json.dumps(detail, indent=1))
    failed = sorted(k for k in detail if k.endswith("_error"))
    if failed:
        log(f"legs failed, no headline: {failed}")
        sys.exit(1)

    print(json.dumps({
        "metric": "tick+assign sustained p99 @ 1M jobs x 10k nodes, 1 chip",
        "value": round(headline_p99, 2),
        "unit": "ms",
        "vs_baseline": round(TARGET_MS / headline_p99, 3),
    }))


if __name__ == "__main__":
    main()
