"""Layer: scheduler step.  ``sched_step_p50_ms`` of the scheduler's
leased metrics snapshot at the close of the window."""


def read(run):
    return run["snapshot"].get("sched_step_p50_ms")
