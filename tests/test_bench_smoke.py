"""Dispatch-plane scaling regression gate (slow tier).

BENCH_r05 found NEGATIVE agent scaling: 2 agents drained 6.2k orders/s
aggregate vs 7.0k/s for one — the plane's store serialized everything
behind one lock and one-wire-frame-per-event delivery.  This smoke runs
``scripts/bench_dispatch.py --quick`` (one past-saturation rate, 1 then
2 agents) and asserts the striped/batched plane scales: 2-agent
aggregate drain >= 1.5x 1-agent.

Marked slow (two short benches + real agent subprocesses); the tier-1
run excludes it.  Needs >= 6 host cores to be meaningful (2 agents +
store + logd + driver), and skips below that.
"""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))


@pytest.mark.slow
def test_warm_takeover_beats_cold_load_at_scale():
    """Checkpoint-plane gate at the CPU-host scale (50k jobs x 512
    nodes): a standby restoring a scheduler checkpoint must take over
    >= 5x faster than the full cold load, restore for real (not fall
    back cold), and dispatch a first window byte-identical to the
    cold-loaded scheduler's — zero divergence."""
    if (os.cpu_count() or 1) < 6:
        pytest.skip("needs >= 6 cores for a meaningful takeover signal")
    import bench_sched
    res = bench_sched.run_bench(
        50_000, 512, steps=3,
        on_log=lambda *a: print(*a, file=sys.stderr))
    assert res.get("failover_warm_restored") == 1, (
        "warm takeover fell back to a cold load: "
        f"{res.get('failover_warm_restored')}")
    cold = res["failover_cold_load_s"]
    warm = res["failover_warm_takeover_s"]
    assert warm * 5 <= cold, (
        f"warm takeover {warm}s is not >= 5x faster than the cold "
        f"load {cold}s")
    assert res.get("failover_warm_divergence_orders") == 0, (
        f"restored scheduler diverged on "
        f"{res.get('failover_warm_divergence_orders')} of "
        f"{res.get('failover_warm_window_orders')} first-window orders")
    assert res.get("failover_warm_window_orders", 0) > 0


@pytest.mark.slow
def test_delta_checkpoint_scales():
    """Incremental-checkpoint gate at the CPU-host scale (50k jobs x
    512 nodes): a DELTA save under sparse churn must be >= 5x faster
    than the full save, the warm takeover (which now folds the chain)
    must still restore for real with zero dispatch divergence, and the
    staggered snapshot's write stall must be bounded (p99 <= 0.25x the
    full-lock hold at the probe's store size, both backends where
    available)."""
    if (os.cpu_count() or 1) < 6:
        pytest.skip("needs >= 6 cores for a meaningful signal")
    import bench_sched
    res = bench_sched.run_bench(
        50_000, 512, steps=3,
        on_log=lambda *a: print(*a, file=sys.stderr))
    assert res.get("failover_warm_restored") == 1
    assert res.get("failover_warm_divergence_orders") == 0, (
        f"restored scheduler diverged on "
        f"{res.get('failover_warm_divergence_orders')} of "
        f"{res.get('failover_warm_window_orders')} first-window orders")
    full = res["sched_checkpoint_save_s"]
    delta = res["sched_checkpoint_delta_save_s"]
    assert delta * 5 <= full, (
        f"delta save {delta}s is not >= 5x faster than the full save "
        f"{full}s (ladder {res.get('sched_checkpoint_delta_ladder_s')})")

    import bench_store
    stall = bench_store.run_stall_suite(
        n_keys=100_000, on_log=lambda *a: print(*a, file=sys.stderr))
    checked = 0
    for backend in ("py", "native"):
        ratio = stall.get(f"snapshot_stall_ratio_{backend}")
        if ratio is None:
            continue
        checked += 1
        assert ratio <= 0.25, (
            f"{backend} staggered write-stall p99 is {ratio}x the "
            f"full-lock hold (bound 0.25x): {stall}")
    assert checked, f"no backend produced a stall ratio: {stall}"


@pytest.mark.slow
def test_two_agents_scale_aggregate_drain():
    if (os.cpu_count() or 1) < 6:
        pytest.skip("needs >= 6 cores for a meaningful scaling signal")
    import bench_dispatch
    res = bench_dispatch.run_quick(
        seconds=3, on_log=lambda *a: print(*a, file=sys.stderr))
    assert res["agg_1_agent_per_s"] > 0
    assert res["scaling_2_over_1"] >= 1.5, (
        f"negative/flat agent scaling regressed: 2 agents drained "
        f"{res['agg_2_agents_per_s']}/s vs {res['agg_1_agent_per_s']}/s "
        f"for one (ratio {res['scaling_2_over_1']})")
    # the quick gate is wider than the scaling ratio: per-agent
    # fairness and the watch frames/event ratio also trip it — the two
    # ways a routing regression that serializes one shard (or one
    # agent) shows up without flattening the 2-over-1 curve
    assert res["quick_gate_failures"] == [], res["quick_gate_failures"]


def test_bench_sched_dag_smoke():
    """Tier-1 smoke for the workflow-DAG bench: a quick 3-stage
    fan-out/fan-in workload must complete with NONZERO chain fires
    delivered exactly once (no duplicates, no misses), zero publish
    failures, and a zero-divergence warm takeover — the DAG plane and
    the bench that measures it both stay alive."""
    import bench_sched
    res = bench_sched.run_dag_bench(
        n_jobs=300, n_nodes=8, rounds=2, window_s=2,
        on_log=lambda *a: print(*a, file=sys.stderr))
    assert res["dag_fires_total"] > 0
    assert res["dag_fires_total"] == res["dag_expected_fires"]
    assert res["dag_duplicate_fires"] == 0
    assert res["dag_missing_fires"] == 0
    assert res["dag_incomplete_rounds"] == 0
    assert res["dag_publish_failures"] == 0
    assert res["dag_warm_restored"] == 1
    assert res["dag_warm_divergence_orders"] == 0
    assert res["dag_chain_p99_ms"] > 0


def test_bench_sched_trace_smoke():
    """Tier-1 smoke for the trace-plane bench (ISSUE 14 satellite): a
    quick live-fleet run must assemble per-stage latencies from real
    sampled spans (every wire stage present, durations non-negative)
    and the paired sampling-overhead leg must produce both arms.  The
    < 2% gate itself runs at the 50k x 512 shape (slow tier / bench.py
    full runs) — single-step timings at this toy shape are noise."""
    import bench_sched
    res = bench_sched.run_trace_bench(
        n_jobs=800, n_nodes=32, steps=4, window_s=2, traced_jobs=12,
        seconds=4, on_log=lambda *a: print(*a, file=sys.stderr))
    assert res["trace_stage_fires"] > 0
    stages = res["trace_stage_p99_ms"]
    for st in ("publish", "claim", "queue", "run", "record"):
        assert st in stages, f"stage {st} missing from {stages}"
        assert stages[st] >= 0.0
    assert res["trace_overhead_on_p99_ms"] > 0
    assert res["trace_overhead_off_p99_ms"] > 0


@pytest.mark.slow
def test_bench_sched_trace_overhead_gate():
    """ISSUE 14 acceptance: at 50k jobs x 512 nodes, head sampling at
    the default shift costs < 2% step p99 vs CRONSUN_TRACE=off
    (trace_shift=-1 — the exact construction-time effect of the env
    switch, byte-identical order wire pinned by test_trace)."""
    import bench_sched
    res = bench_sched.run_trace_bench(
        n_jobs=50_000, n_nodes=512, steps=12, window_s=4,
        traced_jobs=64, seconds=6,
        on_log=lambda *a: print(*a, file=sys.stderr))
    assert res["trace_stage_fires"] > 0
    assert res["trace_overhead_gate_ok"] == 1, (
        f"sampling-on p99 {res['trace_overhead_on_p99_ms']}ms vs off "
        f"{res['trace_overhead_off_p99_ms']}ms (ratio "
        f"{res['trace_overhead_ratio']})")


def test_bench_refuses_a_backend_that_is_not_the_chip():
    """bench.py records chip numbers: on any other backend it exits
    non-zero before any leg, prints no headline, and never imports JAX
    in the parent (a probe child names the backend)."""
    import subprocess
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), "--quick"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 2, out.stderr[-2000:]
    assert out.stdout == ""
    assert "refusing" in out.stderr and "'cpu'" in out.stderr


def test_bench_query_smoke():
    """Tier-1 smoke for the read-plane bench: a short run against one
    py-logd shard with concurrent readers and a full-drain writer must
    complete with NONZERO queries/s on every dashboard shape and zero
    read/write errors — the query path stays alive under ingest, and
    the bench itself stays runnable."""
    os.environ["BENCH_LOGD"] = "py"
    try:
        import bench_query
        # >= 3 readers: shapes are reader-dedicated round-robin, so
        # fewer readers would leave a shape undriven
        # the window opens at the writer's first beat (not at its
        # spawn), and is long enough for each reader's own connect +
        # first query on a host shared with five other xdist workers
        res = bench_query.run_query_bench(
            logd_shards=1, readers=3, seconds=3.0, seed_records=1000,
            on_log=lambda *a: print(*a, file=sys.stderr))
    finally:
        os.environ.pop("BENCH_LOGD", None)
    assert res["query_plane_read_errors"] == 0
    assert res["query_plane_write_errors"] == 0
    for shape in ("latest", "history", "stat_days"):
        assert res[f"query_plane_{shape}_qps"] > 0, (
            f"no {shape} queries completed")
    assert res["query_plane_write_records_per_s"] > 0


def test_bench_push_smoke():
    """Tier-1 smoke for the push-plane bench: a short run with a small
    SSE fleet against one py-logd shard must connect every viewer,
    deliver pushed events (nonzero lag samples), and complete the poll
    comparison without errors — the live-push path stays runnable end
    to end over the real wire."""
    os.environ["BENCH_LOGD"] = "py"
    try:
        import bench_push
        res = bench_push.run_push_bench(
            viewers=20, seconds=1.5, write_rate=50, poll_viewers=3,
            on_log=lambda *a: print(*a, file=sys.stderr))
    finally:
        os.environ.pop("BENCH_LOGD", None)
    assert res["push_plane_viewers_connected"] == 20
    assert res["push_plane_connect_errors"] == 0
    assert res["push_plane_lag_samples"] > 0
    assert res["push_plane_events_per_viewer_s"] > 0
    assert res["push_plane_poll_errors"] == 0
    assert res["push_plane_publish_lag_p99_ms"] > 0
