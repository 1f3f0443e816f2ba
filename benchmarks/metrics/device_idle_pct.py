"""Layer: device.  1 - union of the device's operation intervals over
the traced stretch."""


def read(run):
    tr = run["trace"]
    if not tr or not tr["window_s"] or not tr["busy_s"]:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0
