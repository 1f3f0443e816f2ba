"""Layer: scheduler step.  ``step_span_flush_elig_p50_ms``: the flush's
eligibility part (dirty rows, padding, the scatter), over a leader's
steps that had eligibility rows to write."""


def read(run):
    return run["snapshot"].get("step_span_flush_elig_p50_ms")
