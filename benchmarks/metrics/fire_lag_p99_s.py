"""End to end.  99th percentile of scheduled second -> execution start
over every execution of a judged second on a live agent: the last
executions of the :00 herd on each agent, so it reads how long the
agents take to drain the herd."""


def read(run):
    return run["fire_lag_p99_s"]
