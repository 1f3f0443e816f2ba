"""Layer: scheduler step.  ``gc_pause_ms_leading_total``: ms of the
scheduler process's collector passes, every generation, while it led;
each pass stops every thread of the process."""


def read(run):
    return run["snapshot"].get("gc_pause_ms_leading_total")
