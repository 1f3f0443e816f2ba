"""End to end.  Process start -> first published window, the sleep that
places the window in the minute left out."""


def read(run):
    return run["setup_s"]
