"""One run of one cell: build its world, warm up, drive the measured window,
check every answer against the plain reference, read the metrics.

``run.py`` calls :func:`run_cell` once it has found the chips the cell
asks for; the tests call it on the CPU at a small size.
"""
from __future__ import annotations

import gc
import sys
import tempfile
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from bench import data, reference, spec, traffic
from bench import trace as tracing

# A traced run traces this much of its window at most: a trace holds some
# 90 device events per beam hop, and one second of the largest cell is
# ~100 MB of trace.
TRACE_SECONDS = 3.0


@dataclass
class Run:
    """What the metric readers see of one run."""
    cell: spec.Cell
    setup_s: float
    window: traffic.Window
    rows: int
    state_bytes: int                # sum of the DeviceIndex leaves' nbytes
    state_shapes: dict              # leaf name -> shape
    mean_degree: float
    peak_bytes: int | None
    counters: dict                  # BatchReport counts summed over calls
    check: dict                     # reference.compare's numbers
    traced: bool = False
    trace: tracing.Summary | None = None
    peaks: dict | None = None


@dataclass
class World:
    base: np.ndarray
    pool: np.ndarray
    searcher: object
    state_bytes: int
    state_shapes: dict
    mean_degree: float


class CompileCounter:
    """Counts the compilations JAX reports while ``on`` is set."""

    def __init__(self):
        import jax
        self.on = False
        self.counts = {"compiles": 0, "traces": 0}
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name, secs, **kw):
        if not self.on:
            return
        if name.endswith("backend_compile_duration"):
            self.counts["compiles"] += 1
        elif name.endswith("jaxpr_trace_duration"):
            self.counts["traces"] += 1


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def build_world(config: dict, seed: int, kernels=None) -> World:
    """Data from the seed, the index by the config's world maker, and the
    served entry: ``BatchedSearcher`` with the default ``ServeConfig``."""
    import jax
    from repro.core.search.beam import SearchParams
    from repro.kernels.dispatch import KernelConfig
    from repro.serve.ann import BatchedSearcher

    base = data.make_vectors(config["data"], config["rows"], config["dim"],
                             seed)
    pool = data.make_queries(config["data"], config["query_pool"],
                             config["dim"], seed).astype(np.float32)
    index = spec.world_maker(config["world"])(config, base, seed)
    p = SearchParams(l_size=config["search_list"],
                     beam_width=config["beam_width"], k=config["k"],
                     rerank_batch=config["rerank_batch"],
                     r_max=config["graph_degree"], universe=config["rows"],
                     visited_hash_bits=config["visited_hash_bits"],
                     kernels=kernels or KernelConfig())
    leaves = {name: x for name, x in zip(index._fields, index)
              if x is not None}
    return World(base=base, pool=pool, searcher=BatchedSearcher(index, p),
                 state_bytes=sum(int(x.nbytes) for x in leaves.values()),
                 state_shapes={k: tuple(x.shape) for k, x in leaves.items()},
                 mean_degree=float(jax.numpy.mean(index.counts)))


def warm_up(searcher, pool: np.ndarray, mix: dict) -> None:
    """One call of every batch size the mix sends, so that every bucket
    program its calls are planned into is compiled (or loaded) now."""
    sizes = ([int(mix["clients"])] if mix["loop"] == "closed"
             else range(1, int(mix["max_batch"]) + 1))
    for nq in sizes:
        searcher.search(pool[:nq])


class Served:
    """The searcher as the loops call it: ``queries -> (ids, dists)``. Each
    call's ``BatchReport`` counts are summed into ``counts`` as it returns,
    with the rows dispatched (bucket sizes, padding included), so the
    window keeps no report alive."""

    def __init__(self, searcher):
        self.searcher = searcher
        self.counts = {"rows_dispatched": 0}

    def __call__(self, queries):
        ids, dists, rep = self.searcher.search(queries)
        self.counts["rows_dispatched"] += sum(rep.buckets)
        for k, v in vars(rep).items():
            if isinstance(v, int) and not isinstance(v, bool):
                self.counts[k] = self.counts.get(k, 0) + v
        return ids, dists


class GcPauses:
    """The interpreter's collections while ``on``: (generation, s, at)."""

    def __init__(self):
        self.on, self.pauses, self._t = False, [], 0.0
        gc.callbacks.append(self._event)

    def _event(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t, self._t))

    def close(self):
        gc.callbacks.remove(self._event)


def settle() -> None:
    """End of set-up: what set-up made (imports, traced programs, the index
    build) lives as long as the process, as in a server after start-up, so
    it is collected once and frozen out of the collector's later walks."""
    gc.collect()
    gc.freeze()


def device_info() -> dict:
    import jax
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs),
            "memory_peak_bytes": max(peaks) if peaks else None}


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, kernels=None) -> dict:
    """One run. Returns the result line's object."""
    import jax
    config, mix = cell.config, cell.mix
    counter = CompileCounter()
    world = build_world(config, seed, kernels)
    log(f"bench: {cell.name}: world of {config['rows']} rows, "
        f"{world.state_bytes} B of device state, mean degree "
        f"{world.mean_degree:.2f}, kernels {tuple(world.searcher.p.kernels)}")
    warm_up(world.searcher, world.pool, mix)
    served = Served(world.searcher)
    settle()
    pauses = GcPauses()
    if trace:
        seconds = min(seconds, TRACE_SECONDS)
        tmp = tempfile.TemporaryDirectory(prefix="bench-trace-")
        tracer = tracing.Tracer(Path(tmp.name))
        tracer.start()
    counter.on = pauses.on = True
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        window = traffic.LOOPS[mix["loop"]](
            served, world.pool, mix, seconds, seed,
            span=jax.profiler.TraceAnnotation)
    counter.on = pauses.on = False
    pauses.close()
    gc.unfreeze()
    summary = None
    if trace:
        path = tracer.stop()
        kernels_read = sorted(p.stem for p in
                              (spec.BENCH_DIR / "kernels").glob("*.py"))
        summary = tracing.summarize(tracing.read(path), kernels_read)
        tmp.cleanup()
        if summary is None and jax.devices()[0].platform != "cpu":
            raise RuntimeError("the trace holds no device events")
    dev = device_info()
    base, pool = world.base, world.pool
    counts = served.counts
    shape = dict(rows=config["rows"], state_bytes=world.state_bytes,
                 state_shapes=world.state_shapes,
                 mean_degree=world.mean_degree)
    del world, served   # the program's state goes before the reference runs
    gc.collect()
    lat = window.latencies()
    log(f"bench: window {window.seconds:.3f} s, {len(window.calls)} calls, "
        f"{len(lat)} queries answered, {window.unanswered} unanswered; "
        f"compiles in window {counter.counts['compiles']}, traces "
        f"{counter.counts['traces']}")
    if len(lat):
        pct = np.percentile(lat, [50, 90, 95, 99]) * 1e3
        log("bench: latency from due time p50 {:.3f}, p90 {:.3f}, p95 {:.3f},"
            " p99 {:.3f}, max {:.3f} ms".format(*pct, 1e3 * lat.max()))
    if window.calls:
        dur = np.array([c.end - c.start for c in window.calls]) * 1e3
        top = int(dur.argmax())
        log(f"bench: calls median {np.median(dur):.3f} ms, longest "
            f"{dur[top]:.3f} ms ({len(window.calls[top].rows)} queries at "
            f"{window.calls[top].start:.3f} s), "
            f"{int((dur > 3 * np.median(dur)).sum())} over 3x the median")
    if pauses.pauses:
        gen, longest, at = max(pauses.pauses, key=lambda p: p[1])
        log(f"bench: {len(pauses.pauses)} collections in the window, "
            f"{1e3 * sum(p[1] for p in pauses.pauses):.3f} ms in all, "
            f"longest {1e3 * longest:.3f} ms (generation {gen} at "
            f"{at - t_window:.3f} s)")
    if window.lateness:
        late = np.asarray(window.lateness) * 1e3
        log(f"bench: generator woke late by median {np.median(late):.3f} ms,"
            f" p99 {np.percentile(late, 99):.3f} ms, max {late.max():.3f} ms"
            f" over {len(late)} waits")
    t_ref = time.perf_counter()
    check = reference.compare(base, pool, window, config)
    log(f"bench: reference over {check['answers']} answers in "
        f"{time.perf_counter() - t_ref:.2f} s; recall@{config['k']} "
        f"{check['recall']!r} on {check['recall_answers']} answers")
    peaks = spec.peaks(dev["kind"]) if dev["platform"] != "cpu" else None
    run = Run(cell=cell, setup_s=setup_s, window=window,
              peak_bytes=dev["memory_peak_bytes"],
              counters=counts, check=check,
              traced=trace, trace=summary, peaks=peaks, **shape)
    return result(run, dev)


def result(run: Run, dev: dict) -> dict:
    """The result line: the cell's metrics (end-to-end, or per-layer in a
    traced run), the device, and every compared number beside its limit,
    which also go to stderr as its last lines."""
    cell = run.cell
    metrics = {}
    for m in (cell.per_layer if run.traced else cell.end_to_end):
        v = spec.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    checks = {name: {"value": run.check[name], "limit": limit}
              for name, limit in cell.config["limits"].items()
              if limit is not None}
    attempted = run.window.attempted
    failed = run.window.unanswered + run.check["bad_answers"]
    correct = (attempted > 0 and failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    out = {"correct": bool(correct), "attempted": attempted,
           "failed": failed, "metrics": metrics, "device": dev}
    if run.traced and run.trace:
        out["device"] = dict(dev, busy_s=run.trace.busy_s,
                             window_s=run.trace.window_s)
        out["breakdown"] = {"device_ops": run.trace.device_ops,
                            "idle_gaps": run.trace.idle_gaps}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r})")
    return out
