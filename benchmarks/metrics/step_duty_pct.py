"""Layer: scheduler step.  ``step_duty_pct``: the step's "total" spans
over those plus the loop's "wait" spans (the wait on the clock): how
near the scheduler is to its knee."""


def read(run):
    return run["snapshot"].get("step_duty_pct")
