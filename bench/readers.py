"""Arithmetic shared by the metric readers under ``bench/metrics/``."""
from __future__ import annotations

import numpy as np

from bench import spec


def mean_call_ms(run) -> float | None:
    """Mean host-clock time of the window's ``search`` calls."""
    calls = run.window.calls
    if not calls:
        return None
    return 1e3 * float(np.mean([c.end - c.start for c in calls]))


def latency_ms(run, q: float) -> float | None:
    """The q-th percentile over every query of the window of the time from
    its due time to the return of its call (ms)."""
    lat = run.window.latencies()
    return 1e3 * float(np.percentile(lat, q)) if len(lat) else None


def idle_share(run) -> float | None:
    """Share of the traced window in which no op ran on the device (%)."""
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def roofline_share(run, kernel: str) -> float | None:
    """The least time the chip could take for the work the algorithm needs
    (``bench/kernels/<kernel>.py``), as a share of the kernel's device time
    in the trace (%). Nothing where the trace has no launch of it."""
    t = run.trace
    if t is None or run.peaks is None or t.kernel_s.get(kernel, 0.0) <= 0:
        return None
    cost = spec.load_module(spec.BENCH_DIR / "kernels" / f"{kernel}.py")
    work = cost.work(run)
    if work is None:
        return None
    ops, nbytes = work
    least = max(ops / run.peaks[cost.PEAK],
                nbytes / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / t.kernel_s[kernel]


def lists_fetched(run) -> int | None:
    """Adjacency lists the traversal fetched in the window: the LRU replay's
    uncached reads plus its hits."""
    c = run.counters
    if "graph_ios" not in c or "cache_hits" not in c:
        return None
    return c["graph_ios"] + c["cache_hits"]
