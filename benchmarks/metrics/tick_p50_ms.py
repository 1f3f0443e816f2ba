"""Layer: planner.  Host clock around the window fetch
(``tick_p50_ms`` of the snapshot)."""


def read(run):
    return run["snapshot"].get("tick_p50_ms")
