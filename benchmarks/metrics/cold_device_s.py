"""Layer: entry points.  ``cold_device_s``: the mirrors' first listing
and the first ``_flush_device``, waited for until the device holds the
table."""


def read(run):
    return run["snapshot"].get("cold_device_s")
