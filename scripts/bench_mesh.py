"""Mesh latency ladder: tick+assign over the 1-D and 2-D device meshes,
replicated-waterfill vs bucket-sharded bidding, across device counts.

The multichip dry run (``__graft_entry__.dryrun_multichip``) proves the
collective program compiles and fires; it does not MEASURE how the
assign sweep's inter-chip traffic scales with the fired bucket.  This
bench puts numbers on it:

- tick+assign p50/p99 per (device count, mesh kind, reconcile path),
  both sync per-tick and the fused windowed cadence;
- per-phase breakdown (bid vs gather vs waterfill/reconcile) from the
  planner's phase microbench at the same shapes;
- the estimated per-round / per-tick collective payload bytes for BOTH
  reconcile paths (the analytic model in
  parallel.mesh.estimate_collective_bytes), so "the all-gather is
  O(fired x 9B) and sharded bidding is O(nodes x 16B)" is a printed
  number, not a docstring claim.

Every config runs in its own subprocess with
``--xla_force_host_platform_device_count=<D>`` (forced-host CPU devices
— the same virtualization tier-1 uses), so the ladder runs anywhere;
on a multi-chip host set ``BENCH_MESH_TPU=1`` to use real chips for
the device counts the host actually has.  CPU-host caveat: forced-host
"devices" share one CPU's cores and memory bus, so absolute latencies
are NOT chip latencies and collectives are memcpys — the bytes model
and the sharded-vs-replicated DELTA are the portable results; absolute
speedups are not measured on the chip (docs/OPERATIONS.md "Mesh
sizing").

    python scripts/bench_mesh.py [--devices 1,2,4,8] [--shapes JxN,...]
        [--ticks T] [--quick] [--out MULTICHIP_ladder.json]

Prints one JSON object on stdout (bench.py merges it into
bench_detail.json); ``--out`` also writes a MULTICHIP-sidecar-format
file stamped with git_rev + UTC timestamp.
"""

import argparse
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# ONE definition of the provenance stamp (bench.py owns it; a format
# change — e.g. a dirty-tree marker — must not diverge between the two)
from bench import git_rev, utc_now  # noqa: E402


# ---------------------------------------------------------------------------
# worker: one config, one process, one JSON line
# ---------------------------------------------------------------------------

def _pctl(xs, q):
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))] if xs else 0.0


def run_worker(cfg: dict) -> None:
    if not cfg.get("tpu"):
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    if cfg.get("dcn"):
        # multi-host DCN rung: each participating host runs this same
        # worker; the coordinator address/process topology comes from the
        # BENCH_MESH_DCN_* env (_spawn passes it through untouched)
        jax.distributed.initialize(
            coordinator_address=os.environ["BENCH_MESH_DCN_COORD"],
            num_processes=int(os.environ.get("BENCH_MESH_DCN_NPROC", "1")),
            process_id=int(os.environ.get("BENCH_MESH_DCN_PID", "0")))
    import numpy as np
    from bench import synth_table
    from cronsun_tpu.parallel.mesh import (Sharded2DTickPlanner,
                                           ShardedTickPlanner, make_mesh,
                                           make_mesh2d)

    D = cfg["devices"]
    assert len(jax.devices()) >= D, (jax.devices(), D)
    J, N = cfg["J"], cfg["N"]
    bucket = cfg["bucket"]
    fmtarg = cfg.get("demand_format", "auto")

    def mk(fmt_):
        if cfg["mesh"] == "2d":
            dj, dn = cfg["dj"], cfg["dn"]
            p = Sharded2DTickPlanner(
                make_mesh2d(dj, dn), job_capacity=J, node_capacity=N,
                max_fire_bucket=bucket,
                shard_bids=cfg["path"] == "sharded", demand_format=fmt_)
        else:
            p = ShardedTickPlanner(
                make_mesh(D), job_capacity=J, node_capacity=N,
                max_fire_bucket=bucket, impl="jnp",
                shard_bids=cfg["path"] == "sharded", demand_format=fmt_)
        rng = np.random.default_rng(0)
        # fire-rate sized so a healthy slice of the bucket fires every
        # tick (the reconcile paths differ exactly in how fired-bucket
        # bytes scale, so an idle table would measure nothing); sparse
        # rungs pin period_lo == period_hi == 1/fire_fraction
        p.set_table(synth_table(p.J, cfg["period_lo"], cfg["period_hi"]))
        p.set_eligibility(rng.integers(
            0, 2**32, (p.J, p.N // 32), dtype=np.uint32))
        p.set_job_meta_full(rng.random(p.J) < 0.5,
                            np.ones(p.J, np.float32))
        p.set_node_capacity_full(np.full(p.N, 1 << 20, np.int32))
        return p

    sp = mk(fmtarg)
    T0 = 1_753_000_000
    sp.plan(T0 - 10)                      # compile + warm
    sp.plan(T0 - 9)
    sp.tick_ms.clear()
    lat = []
    for i in range(cfg["ticks"]):
        s = time.perf_counter()
        p = sp.plan(T0 + i)
        lat.append((time.perf_counter() - s) * 1e3)
    fired = len(p.fired)

    W = cfg["window"]
    win_ms = 0.0
    if W > 1:
        sp.plan_window(T0 + 1000, W)      # compile + warm
        s = time.perf_counter()
        for r in range(cfg["win_reps"]):
            sp.plan_window(T0 + 2000 + r * W, W)
        win_ms = (time.perf_counter() - s) * 1e3 / (cfg["win_reps"] * W)

    est = sp.estimate_collective_bytes(bucket)
    fmt = est["demand_format"]
    # predicted vs COMPILED bytes: the analytic crossover model next to
    # what XLA actually lowered, so model drift is a bench fact
    measured = sp.measured_collective_bytes(bucket)

    # fire-set divergence vs the OTHER demand format on the same seed
    # and tick sequence (the tier-1 smoke asserts this stays zero)
    divergence = None
    if cfg.get("check_divergence") and cfg["path"] == "sharded":
        alt = "dense" if fmt == "compacted" else "compacted"
        divergence = 0
        # replay both planners fresh so carried load/rem_cap histories
        # match tick for tick
        sa, sb = mk(fmt), mk(alt)
        for t in [T0 - 10, T0 - 9] + [T0 + i for i in range(cfg["ticks"])]:
            pa, pb = sa.plan(t), sb.plan(t)
            if (sorted(pa.fired.tolist()) != sorted(pb.fired.tolist())
                    or dict(zip(pa.fired.tolist(), pa.assigned.tolist()))
                    != dict(zip(pb.fired.tolist(), pb.assigned.tolist()))):
                divergence += 1

    prof = sp.profile_phases(bucket, iters=3 if cfg["quick"] else 8)
    rec = {
        "devices": D, "mesh": cfg["mesh"], "path": cfg["path"],
        "jobs": sp.J, "nodes": sp.N, "k_local": est["k_local"],
        "ticks": cfg["ticks"], "fired_per_tick": fired,
        "tick_p50_ms": round(_pctl(lat, 0.50), 3),
        "tick_p99_ms": round(_pctl(lat, 0.99), 3),
        "windowed_ms_per_tick": round(win_ms, 3),
        "collective_bytes_per_round": est["per_round"],
        "collective_bytes_per_tick": est["per_tick"],
        "replicated_bytes_per_round": est["replicated_per_round"],
        "sharded_bytes_per_round": est["sharded_per_round"],
        "compacted_bytes_per_round": est["compacted_per_round"],
        "demand_format": fmt,
        "demand_format_requested": fmtarg,
        "predicted_bytes_per_tick": est["per_tick"],
        "measured_bytes_per_tick": measured,
        **{f"phase_{k}": v for k, v in prof.items()},
    }
    if cfg.get("fire_fraction") is not None:
        rec["fire_fraction"] = cfg["fire_fraction"]
    if divergence is not None:
        rec["fire_set_divergence"] = divergence
    if cfg.get("dcn"):
        rec["dcn_processes"] = jax.process_count()
    print(json.dumps(rec))


# ---------------------------------------------------------------------------
# parent: the ladder
# ---------------------------------------------------------------------------

def _spawn(cfg: dict, timeout: float):
    env = dict(os.environ)
    prior = [f for f in env.get("XLA_FLAGS", "").split()
             if not f.startswith("--xla_force_host_platform_device_count")]
    if not cfg.get("tpu"):
        env["JAX_PLATFORMS"] = "cpu"
        prior = [f"--xla_force_host_platform_device_count={cfg['devices']}"
                 ] + prior
    env["XLA_FLAGS"] = " ".join(prior)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--worker",
         json.dumps(cfg)],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(
            f"bench_mesh worker {cfg['devices']}dev/{cfg['mesh']}/"
            f"{cfg['path']} failed rc={proc.returncode}:\n"
            f"{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _tpu_device_count() -> int:
    """Probe the REAL device count in a subprocess (the parent must not
    import jax — the ladder workers own backend init)."""
    try:
        proc = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(len(jax.devices()))"],
            capture_output=True, text=True, timeout=120, cwd=ROOT)
        return int(proc.stdout.strip())
    except Exception:  # noqa: BLE001 — no chips reachable
        return 0


def run_ladder(devices, shapes, ticks, quick, use_tpu, on_log=log,
               demand_format="auto"):
    if use_tpu:
        # real chips: only the rungs this host can actually form
        have = _tpu_device_count()
        kept = [d for d in devices if d <= have]
        if kept != devices:
            on_log(f"BENCH_MESH_TPU=1: host has {have} devices; "
                   f"running rungs {kept} of {devices}")
        devices = kept
    ladder = []
    for J, N in shapes:
        for D in devices:
            kinds = [("1d", D, 1)]
            if D >= 4 and D % 2 == 0:
                kinds.append(("2d", D // 2, 2))
            for mesh, dj, dn in kinds:
                per = {}
                for path in ("sharded", "replicated"):
                    cfg = dict(
                        devices=D, mesh=mesh, dj=dj, dn=dn, J=J, N=N,
                        path=path,
                        # 2x headroom over the ~J/8 mean fire rate
                        # below, so bursty ticks don't clip the bucket
                        # (a clipped bucket caps the very traffic term
                        # being measured)
                        bucket=max(2048, J // 4), ticks=ticks,
                        window=1 if quick else 4,
                        win_reps=2, quick=quick, tpu=use_tpu,
                        demand_format=demand_format,
                        check_divergence=quick,
                        # ~8-25% of jobs fire per tick: enough candidate
                        # pressure that the bucket is the traffic term
                        period_lo=4, period_hi=12)
                    # per-config error scope: one failed rung must not
                    # discard the completed ones (bench.py's subprocess
                    # sections' contract)
                    try:
                        r = _spawn(cfg, timeout=600)
                    except Exception as e:  # noqa: BLE001
                        on_log(f"{D}dev {mesh} {J}x{N} {path}: "
                               f"FAILED ({e})")
                        ladder.append({
                            "devices": D, "mesh": mesh, "jobs": J,
                            "nodes": N, "path": path,
                            "error": str(e)[-500:]})
                        continue
                    ladder.append(r)
                    per[path] = r
                    on_log(f"{D}dev {mesh} {J}x{N} {path}: "
                           f"p50={r['tick_p50_ms']}ms "
                           f"p99={r['tick_p99_ms']}ms "
                           f"bytes/round={r['collective_bytes_per_round']}"
                           f" fired={r['fired_per_tick']}")
                if len(per) == 2:
                    s, rpl = per["sharded"], per["replicated"]
                    ladder.append({
                        "devices": D, "mesh": mesh, "jobs": s["jobs"],
                        "nodes": s["nodes"], "path": "compare",
                        "bytes_ratio": round(
                            s["collective_bytes_per_round"]
                            / max(1, rpl["collective_bytes_per_round"]),
                            4),
                        "p99_ratio": round(
                            s["tick_p99_ms"]
                            / max(1e-9, rpl["tick_p99_ms"]), 4),
                    })
    return ladder


# sparse-tick rungs: the corner the compacted demand gather targets —
# few fires on wide fleets, where the dense [2, N] exchange pays O(N)
# bytes for O(fired) demand.  fire fraction f is realized through the
# synth table's @every period (uniform phases -> ~J*f candidates/tick)
SPARSE_FRACTIONS = (0.001, 0.01, 0.1)
SPARSE_WIDTHS = (10_000, 100_000)


def run_sparse_ladder(devices, quick, use_tpu, on_log=log,
                      demand_format="auto", dcn=False):
    D = max(devices)
    J = 16_384 if quick else 65_536
    rungs = []
    for N in SPARSE_WIDTHS:
        for f in SPARSE_FRACTIONS:
            period = max(1, round(1 / f))
            cfg = dict(
                devices=D, mesh="1d", dj=D, dn=1, J=J, N=N,
                path="sharded", fire_fraction=f,
                # 4x headroom over the ~J*f mean so bursty ticks don't
                # clip the very bucket term being measured
                bucket=max(2048, int(4 * J * f)),
                ticks=3 if quick else 10, window=1, win_reps=1,
                quick=quick, tpu=use_tpu, dcn=dcn,
                demand_format=demand_format,
                check_divergence=True,
                period_lo=period, period_hi=period)
            try:
                r = _spawn(cfg, timeout=900)
            except Exception as e:  # noqa: BLE001
                on_log(f"sparse {D}dev {J}x{N} f={f}: FAILED ({e})")
                rungs.append({"devices": D, "jobs": J, "nodes": N,
                              "fire_fraction": f, "path": "sharded",
                              "error": str(e)[-500:]})
                continue
            rungs.append(r)
            on_log(f"sparse {D}dev {J}x{N} f={f}: fmt={r['demand_format']}"
                   f" bytes/round={r['collective_bytes_per_round']}"
                   f" (dense={r['sharded_bytes_per_round']}"
                   f" comp={r['compacted_bytes_per_round']})"
                   f" predicted={r['predicted_bytes_per_tick']}"
                   f" measured={r['measured_bytes_per_tick']}"
                   f" divergence={r.get('fire_set_divergence')}")
    return rungs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--worker", metavar="JSON", default=None,
                    help=argparse.SUPPRESS)
    ap.add_argument("--devices", default="1,2,4,8",
                    help="device-count ladder (forced-host CPU devices "
                         "unless BENCH_MESH_TPU=1)")
    ap.add_argument("--shapes", default="65536x1024",
                    help="JxN job/node shapes, comma-joined")
    ap.add_argument("--ticks", type=int, default=20,
                    help="timed sync ticks per config")
    ap.add_argument("--quick", action="store_true",
                    help="tier-1 smoke: 2 devices, small shape, few ticks")
    ap.add_argument("--mesh-demand-format", default="auto",
                    choices=("auto", "dense", "compacted"),
                    help="pin the sharded reconcile's demand wire format "
                         "(auto = per-plan crossover pick; the rollback "
                         "knob for the compacted gather)")
    ap.add_argument("--sparse", action="store_true",
                    help="also run the sparse-tick rungs (fire fractions "
                         f"{SPARSE_FRACTIONS} x widths {SPARSE_WIDTHS}; "
                         "always on in full mode)")
    ap.add_argument("--out", default=None, metavar="FILE",
                    help="also write a MULTICHIP-sidecar-format JSON")
    args = ap.parse_args(argv)

    if args.worker is not None:
        run_worker(json.loads(args.worker))
        return 0

    use_tpu = os.environ.get("BENCH_MESH_TPU") == "1"
    if args.quick:
        devices = [2]
        shapes = [(4096, 128)]
        ticks = 5
    else:
        devices = [int(x) for x in args.devices.split(",") if x]
        shapes = [tuple(int(v) for v in s.lower().split("x"))
                  for s in args.shapes.split(",") if s]
        ticks = args.ticks

    t0 = time.time()
    ladder = run_ladder(devices, shapes, ticks, args.quick, use_tpu,
                        demand_format=args.mesh_demand_format)
    # sparse-tick rungs: always in full mode, opt-in (--sparse) in quick;
    # BENCH_MESH_DCN=1 re-runs them over a jax.distributed multi-host
    # mesh (coordinator topology from BENCH_MESH_DCN_* — the same
    # opt-in-env contract as BENCH_MESH_TPU)
    sparse = []
    if args.sparse or not args.quick:
        sparse = run_sparse_ladder(
            devices, args.quick, use_tpu,
            demand_format=args.mesh_demand_format)
    if os.environ.get("BENCH_MESH_DCN") == "1":
        sparse += run_sparse_ladder(
            [int(os.environ.get("BENCH_MESH_DCN_DEVICES", max(devices)))],
            args.quick, use_tpu,
            demand_format=args.mesh_demand_format, dcn=True)
    measured = [r for r in ladder
                if r.get("path") != "compare" and "error" not in r]
    failed = [r for r in ladder + sparse if "error" in r]
    compares = [r for r in ladder if r.get("path") == "compare"]
    divergences = [r["fire_set_divergence"] for r in ladder + sparse
                   if r.get("fire_set_divergence") is not None]
    out = {
        "multichip_backend": "tpu" if use_tpu else "cpu-forced-host",
        "multichip_devices": devices,
        "multichip_ticks_total": sum(r["ticks"] for r in measured),
        "multichip_failed_configs": len(failed),
        "multichip_ladder": ladder,
        "multichip_sparse_ladder": sparse,
        "multichip_demand_format": args.mesh_demand_format,
        "multichip_divergence_total": sum(divergences),
        "multichip_divergence_checks": len(divergences),
        "multichip_bytes_ratio_worst": max(
            (c["bytes_ratio"] for c in compares), default=0.0),
        "multichip_wall_s": round(time.time() - t0, 1),
        "git_rev": git_rev(),
        "generated_at_utc": utc_now(),
    }
    if args.out:
        tail = "; ".join(
            f"{c['devices']}dev/{c['mesh']}: bytes x{c['bytes_ratio']} "
            f"p99 x{c['p99_ratio']}" for c in compares)
        with open(args.out, "w") as f:
            json.dump({
                "n_devices": max(devices), "rc": 0, "ok": True,
                "skipped": False, "git_rev": out["git_rev"],
                "generated_at_utc": out["generated_at_utc"],
                "tail": f"bench_mesh ladder OK: {tail}",
                "ladder": ladder + sparse,
            }, f, indent=1)
        log(f"sidecar written: {args.out}")
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
