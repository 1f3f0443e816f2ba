"""Layer: scheduler step.  ``first_publish_s``: leader lease won -> the
first window's HWM put acknowledged (the publisher's hwm thread)."""


def read(run):
    return run["snapshot"].get("first_publish_s")
