"""Layer: store.  Mean time of a single-key ``put`` inside the window
(``op_stats`` of the store at close less at open): what a writer waits
behind the batch ops that hold every stripe."""


def read(run):
    a, b = (run["op_stats"][k].get("put", {}) for k in ("open", "close"))
    n = b.get("count", 0) - a.get("count", 0)
    if n <= 0:
        return None
    return (b["total_ms"] - a.get("total_ms", 0.0)) / n
