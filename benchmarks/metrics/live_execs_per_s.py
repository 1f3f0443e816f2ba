"""Layer: agent.  Executions of judged seconds recorded on the live
agents, per judged second: the load the scheduler's placement puts on
the nodes whose lag is measured (the fire lag's sample count over the
judged seconds)."""


def read(run):
    return run["lag_samples"] / run["judged_s"] if run["judged_s"] else None
