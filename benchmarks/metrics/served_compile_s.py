"""Layer: scheduler step.  ``compile_leading_s_total``: the seconds of
the executables counted in ``compiles_leading_total`` (compiled, or
loaded from the cache, while the scheduler led, off the warm thread)."""


def read(run):
    return run["snapshot"].get("compile_leading_s_total")
