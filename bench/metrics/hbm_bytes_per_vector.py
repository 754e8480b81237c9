"""The device's peak bytes in use, read after the window, per index row."""


def read(run):
    return run.peak_bytes / run.rows if run.peak_bytes else None
