"""Set-up: from process start to the start of the measured window (s)."""


def read(run):
    return run.setup_s
