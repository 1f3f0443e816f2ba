"""Layer: agent.  The latest execution start of the run, scheduled
second -> start: the slowest agent's last herd execution.  One sample,
so it stands beside fire_lag_p99_s and is not end to end."""


def read(run):
    return run["fire_lag_max_s"]
