"""Pad rows over rows dispatched to the device, by ``BatchReport`` (%)."""


def read(run):
    c = run.counters
    if not c.get("rows_dispatched") or "n_padded" not in c:
        return None
    return 100.0 * c["n_padded"] / c["rows_dispatched"]
