"""Layer: scheduler step.  ``compiles_leading_flush_total``: the served
path's executables obtained inside the step's device flush (a padded
scatter size the leader had not flushed before)."""


def read(run):
    return run["snapshot"].get("compiles_leading_flush_total")
