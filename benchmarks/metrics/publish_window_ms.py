"""Layer: publisher.  Wire time of the last window published before
the close (``publish_window_ms`` of the snapshot)."""


def read(run):
    return run["snapshot"].get("publish_window_ms")
