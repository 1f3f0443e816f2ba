"""Layer: agent.  Times a live agent had to register again because its
node lease lapsed (its log line), over the whole run.  A cause, not a
verdict: what a lapse costs is a fire lost, which ``failed`` counts."""


def read(run):
    return run["lease_lapses"]
