"""Layer: scheduler step.  ``step_span_flush_p50_ms``: device scatters
for job updates (the agents' avg_time write-backs)."""


def read(run):
    return run["snapshot"].get("step_span_flush_p50_ms")
