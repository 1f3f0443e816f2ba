"""Layer: scheduler step.  ``compiles_leading_total``: executables
compiled, or loaded from the cache, while the scheduler led, on any
thread but the warm thread — each a stall of the served path."""


def read(run):
    return run["snapshot"].get("compiles_leading_total")
