"""A run of each cell on the CPU at a tiny size, with the Pallas kernels in
interpret mode: everything a chip run does below its look for a TPU."""
import time

import pytest

from bench_testing import tiny_cell  # noqa: F401
from bench import harness

CELLS = ["sift-vamana-2k.open", "bigann-share-1m.closed32"]


def pallas_interpret():
    from repro.kernels.dispatch import KernelConfig
    return KernelConfig(*["pallas"] * len(KernelConfig._fields)).resolve()


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("traced", [False, True])
def test_rehearsal(tiny_cell, name, traced):
    cell = tiny_cell(name)
    out = harness.run_cell(cell, 2**31 + 5, 1.0, traced, time.perf_counter(),
                           kernels=pallas_interpret())
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["dist_err"] == {"value": 0.0, "limit": 0.0}
    want = {m["name"] for m in (cell.per_layer if traced
                                else cell.end_to_end)}
    got = set(out["metrics"])
    if traced:
        # no device plane on the CPU: the trace-read metrics stay silent
        trace_read = {m["name"] for m in cell.per_layer
                      if m["source"] == "device_trace"}
        assert got == want - trace_read
        assert "busy_s" not in out["device"]
    else:
        assert got == want - {"hbm_bytes_per_vector"}   # CPU: no peak
    for m in out["metrics"].values():
        assert m["value"] >= 0
