"""Layer: scheduler step.  ``sched_step_cpu_p50_ms``: the step thread's
CPU (``time.thread_time``) inside ``step()``; against ``step_p50_ms`` it
says whether the step computes or waits."""


def read(run):
    return run["snapshot"].get("sched_step_cpu_p50_ms")
