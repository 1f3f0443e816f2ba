"""Layer: entry points.  ``warm_s``: the warm thread's
``cronsun.warm.compile`` span — the window program and the escalation
bucket, compiled or loaded from the cache."""


def read(run):
    return run["snapshot"].get("warm_s")
