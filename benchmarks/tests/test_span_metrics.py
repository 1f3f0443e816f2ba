"""The twelve per-layer readers of PR 27 (``metrics/<name>.py``), each
on a canned ``run``, and ``idle_unnamed_s`` on two traces recorded on
the chip: ``plan32k.xplane.pb`` predates the scheduler's spans (every
gap over 5 ms is unnamed), ``spans2k.xplane.pb`` was recorded with them
(TPU v5 lite, ``rehearsal-2k-64.json`` under ``minute-noalone``, 8 s of
the window)."""

import json
import os
import subprocess
import sys

import pytest

import run as bench_run

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "fleet-24k-1k.minute-noalone"
# reader -> (snapshot key, a reading)
SNAPSHOT = {
    "cold_startup_s": ("cold_startup_s", 9.871),
    "cold_jobs_s": ("cold_jobs_s", 4.204),
    "cold_device_s": ("cold_device_s", 1.013),
    "warm_s": ("warm_s", 2.11),
    "first_publish_s": ("first_publish_s", 0.58),
    "served_compiles": ("compiles_leading_total", 0),
    "step_duty_pct": ("step_duty_pct", 3.4),
    "step_cpu_p50_ms": ("sched_step_cpu_p50_ms", 61.5),
    "alone_left_out": ("alone_left_out_total", 0),
    "fires_node_gone": ("fires_node_gone_total", 3),
}
NEW = sorted(SNAPSHOT) + ["cold_unnamed_s", "idle_unnamed_s"]


def canned():
    snap = {key: value for key, value in SNAPSHOT.values()}
    snap.update(cold_planner_s=0.4, cold_lists_s=0.9)
    return {"snapshot": snap, "cold_load_s": 16.7, "trace": {
        "idle_gaps": [["cronsun.step.wait", 1.8],
                      ["after gather, before dispatch", 0.31],
                      ["cronsun.publish.window + cronsun.step.wait", 1.6],
                      ["after submit, before trace end", 0.02]]}}


@pytest.mark.parametrize("name", sorted(SNAPSHOT))
def test_snapshot_reader(name):
    key, value = SNAPSHOT[name]
    run = canned()
    assert bench_run.read_metric(name, run) == value
    del run["snapshot"][key]
    assert bench_run.read_metric(name, run) is None
    # the parent's scheduler has no such key: nothing raised, no metric
    assert bench_run.read_metric(name, {"snapshot": {}, "trace": None,
                                        "cold_load_s": 1.0}) is None


def test_cold_unnamed_is_the_harness_clock_less_the_named_phases():
    run = canned()
    assert bench_run.read_metric("cold_unnamed_s", run) == pytest.approx(
        16.7 - (9.871 + 0.4 + 0.9 + 4.204 + 1.013))
    del run["snapshot"]["cold_lists_s"]
    assert bench_run.read_metric("cold_unnamed_s", run) is None
    assert bench_run.read_metric("cold_unnamed_s", {
        "snapshot": {}, "cold_load_s": 16.7}) is None


def test_idle_unnamed_sums_the_gaps_no_span_overlapped():
    run = canned()
    assert bench_run.read_metric("idle_unnamed_s", run) == pytest.approx(
        0.33)
    run["trace"]["idle_gaps"] = [["cronsun.step.wait", 1.8]]
    assert bench_run.read_metric("idle_unnamed_s", run) == 0
    assert bench_run.read_metric("idle_unnamed_s", {"trace": None}) is None
    assert bench_run.read_metric("idle_unnamed_s", {"trace": {}}) is None


def test_every_new_metric_is_listed_for_the_cell_and_last():
    bench = bench_run.load_json(os.path.join(bench_run.REPO,
                                             "BENCHMARK.json"))
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == [
        "cold_startup_s", "cold_jobs_s", "cold_device_s", "cold_unnamed_s",
        "warm_s", "first_publish_s", "served_compiles", "step_duty_pct",
        "step_cpu_p50_ms", "alone_left_out", "fires_node_gone",
        "idle_unnamed_s"]
    layers = {m["layer"] for m in bench["per_layer"][:-len(NEW)]}
    for m in bench["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL] and m["layer"] in layers, m
        with open(os.path.join(bench_run.HERE, "metrics",
                               m["name"] + ".py")) as f:
            assert len(f.read().splitlines()) <= 12, m["name"]


def reduce(trace: str) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.join(bench_run.HERE, "tracereduce.py"),
         os.path.join(HERE, "data", trace), "8.0"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_a_trace_from_before_the_spans_is_unnamed():
    tr = reduce("plan32k.xplane.pb")
    gaps = tr["idle_gaps"]
    # the planner's two annotations were there: they name milliseconds
    named = [(label, s) for label, s in gaps if not label.startswith("after ")]
    assert all(s < 0.005 and label.startswith("cronsun.plan.")
               for label, s in named)
    unnamed = bench_run.read_metric("idle_unnamed_s", {"trace": tr})
    assert unnamed == pytest.approx(sum(s for _l, s in gaps)
                                    - sum(s for _l, s in named))
    assert unnamed == pytest.approx(6.676, abs=0.005)


def test_a_trace_with_the_spans_names_its_gaps():
    assert os.path.getsize(os.path.join(
        HERE, "data", "spans2k.xplane.pb")) < 1_500_000
    tr = reduce("spans2k.xplane.pb")
    assert bench_run.read_metric("idle_unnamed_s", {"trace": tr}) < 0.1
    label, seconds = tr["idle_gaps"][0]
    assert "cronsun.step.wait" in label.split(" + ") and seconds > 1.0
    for label, seconds in tr["idle_gaps"]:
        assert seconds <= 0.05 or not label.startswith("after "), label
