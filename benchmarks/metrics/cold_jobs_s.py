"""Layer: entry points.  ``cold_jobs_s``: the ``cmd`` listing,
``_apply_job`` over every job and the phase write-back
(``SchedulerService._load_initial``)."""


def read(run):
    return run["snapshot"].get("cold_jobs_s")
