"""Layer: entry points.  Scheduler spawn -> READY (import, connect,
list, parse, device build), harness clock."""


def read(run):
    return run["cold_load_s"]
