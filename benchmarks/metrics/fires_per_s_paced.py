"""Layer: harness.  Due fires of the judged seconds (fleet-wide, plus
the Common executions due on the live nodes) over the judged seconds:
the offered rate — the clock paces these cells."""


def read(run):
    return run["attempted"] / run["judged_s"]
