"""Queries answered in the window over the window's seconds."""


def read(run):
    n = sum(len(c.rows) for c in run.window.calls)
    return n / run.window.seconds if n and run.window.seconds > 0 else None
