"""Bytes of the ``DeviceIndex`` leaves per index row."""


def read(run):
    return run.state_bytes / run.rows
