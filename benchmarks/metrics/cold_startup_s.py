"""Layer: entry points.  ``cold_startup_s``: the OS's start of the
scheduler process (/proc/self/stat) -> ``bin.sched main()`` past the
JAX import and the first ``jax.devices()``; the launcher's own import
and device probe are inside it."""


def read(run):
    return run["snapshot"].get("cold_startup_s")
