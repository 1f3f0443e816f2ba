"""Layer: scheduler step.  ``step_span_flush_table_p50_ms``: the flush's
schedule-table part (rows to numpy, padding, one scatter a field), over
a leader's steps that had table rows to write."""


def read(run):
    return run["snapshot"].get("step_span_flush_table_p50_ms")
