"""Layer: kernels.  The plan program's share of its roofline: the least
time the chip could take for one window of this cell's shapes
(roofline.py: bytes over peak bandwidth — it is memory-bound) over the
device time per window.  Nothing to read without a trace."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import roofline  # noqa: E402


def read(run):
    tr = run["trace"]
    if not tr or not tr["plan_windows"] or not tr["plan_device_s"]:
        return None
    cell = run["cell"]
    fires = run["attempted"] / run["judged_s"] * cell["window_s"]
    least_s, _bound = roofline.plan_window_least_seconds(
        run["device"]["kind"], cell["job_capacity"], cell["nodes"],
        cell["window_s"], fires)
    return least_s / (tr["plan_device_s"] / tr["plan_windows"]) * 100.0
