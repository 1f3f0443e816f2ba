"""The five readers of the scheduler's own pauses: each on a recorded
snapshot of a leading scheduler, each None where the key is absent (the
parent's program), and each listed last for every cell."""

import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
CELLS = ["fleet-24k-1k.minute-noalone", "fleet-100k-10k.minute-noalone",
         "fleet-24k-1k.minute"]
NEW = ["served_compile_s", "flush_compiles", "gc_pause_ms",
       "flush_table_ms", "flush_elig_ms"]

# the pause keys of a leading scheduler's snapshot (shape as it holds
# them): 51 served compiles, 17 of them in the flush
SNAP = {"compiles_leading_total": 51, "compile_leading_s_total": 4.812,
        "compiles_leading_flush_total": 17,
        "compiles_leading_plan_total": 34,
        "gc_pause_ms_leading_total": 212.5,
        "gc_full_ms_leading_total": 0.0, "gc_full_passes_leading_total": 0,
        "step_span_flush_table_p50_ms": 41.25,
        "step_span_flush_elig_p50_ms": 3.5,
        "step_span_flush_p50_ms": 69.7}


def reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "t_" + name, os.path.join(BENCH, "metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run(snapshot=SNAP):
    return {"snapshot": dict(snapshot), "trace": None,
            "device": {"platform": "tpu", "kind": "TPU v5 lite",
                       "count": 1}}


def parent(key: str):
    """The same run from a program that has no such key."""
    return run({k: v for k, v in SNAP.items() if k != key})


def test_every_new_metric_is_last_and_listed_for_every_cell():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = bench["per_layer"][-len(NEW):]
    assert [m["name"] for m in entries] == NEW
    for m in entries:
        assert m["workloads"] == CELLS and m["layer"] == "scheduler step"
        assert m["moves"] == "fire_lag_p99_s" and m["better"] == "lower"
    assert {m["name"]: m["source"] for m in entries} == {
        "served_compile_s": "program_counter",
        "flush_compiles": "program_counter",
        "gc_pause_ms": "program_counter",
        "flush_table_ms": "program_span", "flush_elig_ms": "program_span"}


def test_served_compile_s():
    read = reader("served_compile_s")
    assert read(run()) == pytest.approx(4.812)
    assert read(parent("compile_leading_s_total")) is None


def test_flush_compiles():
    read = reader("flush_compiles")
    assert read(run()) == 17
    assert read(run({**SNAP, "compiles_leading_flush_total": 0})) == 0
    assert read(parent("compiles_leading_flush_total")) is None


def test_gc_pause_ms():
    read = reader("gc_pause_ms")
    assert read(run()) == pytest.approx(212.5)
    assert read(parent("gc_pause_ms_leading_total")) is None


def test_flush_table_ms():
    read = reader("flush_table_ms")
    assert read(run()) == pytest.approx(41.25)
    # a part that never had work reads 0.0, not None
    assert read(run({**SNAP, "step_span_flush_table_p50_ms": 0.0})) == 0.0
    assert read(parent("step_span_flush_table_p50_ms")) is None


def test_flush_elig_ms():
    read = reader("flush_elig_ms")
    assert read(run()) == pytest.approx(3.5)
    assert read(parent("step_span_flush_elig_p50_ms")) is None
