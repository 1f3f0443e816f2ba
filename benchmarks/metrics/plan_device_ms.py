"""Layer: kernels.  Device time per plan window: the device durations
of the operations under the scopes cronsun.fire_mask / compact / fanout
/ assign (and the loop that carries them) in the traced stretch, over
the plan windows dispatched in it."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["plan_windows"] or not tr["plan_device_s"]:
        return None
    return tr["plan_device_s"] / tr["plan_windows"] * 1e3
