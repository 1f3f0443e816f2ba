"""Layer: publisher.  Median per step of the builder's submit plus the
step's stall behind it (``step_span_submit_p50_ms`` +
``step_span_stall_p50_ms``): the publisher's backpressure."""


def read(run):
    snap = run["snapshot"]
    got = [snap.get(k) for k in ("step_span_submit_p50_ms",
                                 "step_span_stall_p50_ms")]
    got = [v for v in got if v is not None]
    return sum(got) if got else None
