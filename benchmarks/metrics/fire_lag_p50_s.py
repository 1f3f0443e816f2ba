"""Layer: agent.  Median of scheduled second -> execution start over
every execution of a judged second on a live agent (result records).
Not end to end: a third of the executions are the :00 herd's, so this
median sits on the upper ramp of the steady seconds' lag and swings
with the agents' load (PERF.md section 2)."""


def read(run):
    return run["fire_lag_p50_s"]
