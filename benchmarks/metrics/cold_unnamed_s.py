"""Layer: entry points.  The harness's ``cold_load_s`` (sched spawn ->
READY) less the five phases the scheduler names inside it: the spawn,
and whatever of the cold load no phase names."""

PHASES = ("startup", "planner", "lists", "jobs", "device")


def read(run):
    named = [run["snapshot"].get(f"cold_{k}_s") for k in PHASES]
    if None in named or run.get("cold_load_s") is None:
        return None
    return run["cold_load_s"] - sum(named)
