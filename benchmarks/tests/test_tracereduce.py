"""The reduction from a profiler trace to the device numbers, on a
trace recorded on the chip: TPU v5 lite, the 32,000 x 1,024 replay of
PR 26's first chip call, 8 s of the window = two plan windows
(``data/plan32k.xplane.pb``, 1.4 MB)."""

import os
import subprocess
import sys
import json

import pytest

import roofline
import tracereduce

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE = os.path.join(HERE, "data", "plan32k.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    # in a process of its own, as run.py calls it: JAX stays out of the
    # caller and off the accelerator
    out = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(HERE),
                                      "tracereduce.py"), TRACE, "8.0"],
        capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_busy_and_window(reduced):
    assert reduced["window_s"] == 8.0
    assert reduced["busy_s"] == pytest.approx(0.0855562, rel=1e-4)
    assert reduced["devices"] == 1


def test_plan_windows_by_scope(reduced):
    assert reduced["plan_windows"] == 2
    assert reduced["plan_device_s"] == pytest.approx(0.0850789, rel=1e-4)
    assert set(reduced["scope_s"]) == {
        "cronsun.fire_mask", "cronsun.compact", "cronsun.fanout",
        "cronsun.assign"}
    # nothing can be busier than the device was
    assert reduced["plan_device_s"] <= reduced["busy_s"]


def test_breakdown(reduced):
    ops, gaps = reduced["device_ops"], reduced["idle_gaps"]
    assert 1 <= len(ops) <= 10 and 1 <= len(gaps) <= 10
    assert ops[0][0] == "%while.35" and ops[0][1] > ops[1][1]
    assert any("[cronsun.compact]" in name for name, _s in ops)
    assert all(len(name) <= 100 for name, _s in ops)
    # the longest gap is the wait between two plan windows
    assert gaps[0][0] == "after gather, before dispatch"
    assert 3.5 < gaps[0][1] < 4.0
    assert sum(g for _n, g in gaps) < reduced["window_s"]


def test_scopes_come_from_the_event_metadata():
    scopes = tracereduce.op_scopes(TRACE)
    assert len(scopes) > 50
    assert set(scopes.values()) <= {
        "cronsun.fire_mask", "cronsun.compact", "cronsun.fanout",
        "cronsun.assign"}


def test_roofline_share_is_a_share(reduced):
    least, bound = roofline.plan_window_least_seconds(
        "TPU v5 lite", 32768, 1024, 4, 850 * 4)
    assert bound == "memory"
    share = least / (reduced["plan_device_s"] / reduced["plan_windows"])
    assert 0 < share * 100 < 1
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9")
