"""Layer: store.  Share of the window the store spent fanning events
out to watchers under the revision lock (``watch_fanout`` total_ms of
``op_stats``, close less open)."""


def read(run):
    a, b = (run["op_stats"][k].get("watch_fanout", {})
            for k in ("open", "close"))
    if "total_ms" not in b:
        return None
    ms = b["total_ms"] - a.get("total_ms", 0.0)
    return ms / (run["window_seconds"] * 1e3) * 100.0
