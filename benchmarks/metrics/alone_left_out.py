"""Layer: scheduler step.  ``alone_left_out_total``: Alone fires the
order build dropped because the lock mirror read live at build time
(PERF.md, Open questions, row 1)."""


def read(run):
    return run["snapshot"].get("alone_left_out_total")
