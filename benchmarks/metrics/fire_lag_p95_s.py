"""Layer: agent.  95th percentile of scheduled second -> execution start
over every execution of a judged second on a live agent.  Not end to
end: it sits on the steep middle of the herd's ramp, where the split
of the herd over the agents moves it 18-27 % run to run (PERF.md
section 2)."""


def read(run):
    return run["fire_lag_p95_s"]
