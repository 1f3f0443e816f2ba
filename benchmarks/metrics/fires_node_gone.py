"""Layer: scheduler step.  ``fires_node_gone_total``: exclusive fires
dropped at the order build because the node they were placed on had
left the fleet."""


def read(run):
    return run["snapshot"].get("fires_node_gone_total")
