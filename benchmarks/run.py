"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Mapping:
  bench_entropy      Table 1        bench_search       Exp#3/#4 (Fig 7/8)
  bench_storage      Exp#2 (Fig 6)  bench_update       Exp#5/#7 (Fig 9/10)
  bench_components   Exp#1 (Fig 5)  bench_compression  Exp#8 (Fig 11)
  bench_breakdown    Exp#6 (Tab 3)  bench_roofline     §Roofline (dry-run)
  bench_kernels      Pallas kernel oracles
  bench_serve_ann    Serving path: QPS vs batch size vs shard count
  bench_serve        Admission tier: SLO tails under Poisson vs bursty load
  bench_shard        Mesh-scale sharding: QPS vs shards, routing, hier merge

JSON artifacts (written in-harness, one per experiment family):
  bench_storage     -> BENCH_storage.json     (planner vs fixed vs colocated)
  bench_compression -> BENCH_compression.json (codec sizes + decision table)
  bench_update      -> BENCH_update.json      (merge/write-amp arms)
  bench_kernels     -> BENCH_kernels.json     (ref vs pallas vs auto-tuned)
  bench_serve       -> BENCH_serve.json       (modeled p50/p95/p99 + QPS +
                                               bursty-over-poisson p99 gate)
  bench_search      -> BENCH_search.json      (blocking vs pipelined vs
                                               pipelined+coresident arms at
                                               pinned-equal recall)
  bench_shard       -> BENCH_shard.json       (QPS-vs-shards scaling curve,
                                               route_frac sweep, failed-
                                               shard arm, scaling-eff gate)

``python -m benchmarks.run --summary`` folds every BENCH_*.json in the
working directory into one trajectory row appended to ``BENCH_summary.json``
(git rev + per-family headline numbers), so successive runs accumulate a
perf history instead of overwriting each other.
"""
import glob
import json
import os
import subprocess
import sys
import time
import traceback

SUMMARY_OUT = "BENCH_summary.json"
MAX_ROWS = 50          # trajectory depth kept in the summary file


def _digest(name: str, doc: dict):
    """One family's headline numbers — small enough to diff by eye."""
    if name == "BENCH_kernels.json":
        auto = doc.get("auto_tuned", {})
        return dict(
            platform=doc.get("platform"),
            pallas_resolved_as=doc.get("pallas_resolved_as"),
            auto_tuned_never_loses=auto.get("never_loses"),
            auto_tuned_picks={f"{r['op']}|{r['size']}": r["resolved"]
                              for r in auto.get("rows", [])},
            e2e_qps={r["backend"]: r.get("qps")
                     for r in doc.get("e2e", [])},
            rerank_regression_us={
                f"{r['backend']}": r["us"] for r in doc.get("ops", [])
                if r["op"] == "rerank_l2" and "c=130" in r["size"]})
    if name == "BENCH_storage.json":
        return dict(suite=doc.get("suite"))
    if name == "BENCH_search.json":
        return dict(
            suite=doc.get("suite"),
            latency_us={k: v.get("latency_us")
                        for k, v in doc.get("arms", {}).items()},
            blocks_per_hop={k: v.get("blocks_per_hop")
                            for k, v in doc.get("arms", {}).items()})
    if name == "BENCH_shard.json":
        suite = doc.get("suite", {})
        return dict(
            suite=suite,
            qps_vs_shards=suite.get("qps"),
            route_sweep={k: v.get("recall")
                         for k, v in doc.get("route_sweep", {}).items()})
    if name == "BENCH_serve.json":
        return dict(
            suite=doc.get("suite"),
            qps={k: v.get("qps") for k, v in doc.get("traces", {}).items()},
            p99_us={k: v.get("latency_us", {}).get("p99")
                    for k, v in doc.get("traces", {}).items()},
            miss_rate={k: v.get("miss_rate")
                       for k, v in doc.get("traces", {}).items()})
    # Generic family: keep the scalar top-level fields only.
    return {k: v for k, v in doc.items()
            if isinstance(v, (int, float, str, bool))}


def summarize(out: str = SUMMARY_OUT) -> dict:
    """Fold all BENCH_*.json into one trajectory row in ``out``."""
    files = {}
    for path in sorted(glob.glob("BENCH_*.json")):
        base = os.path.basename(path)
        if base == os.path.basename(out):
            continue
        try:
            with open(path) as f:
                doc = json.load(f)
        except ValueError:
            files[base] = {"error": "unreadable"}
            continue
        files[base] = _digest(base, doc)
    try:
        rev = subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                             capture_output=True, text=True,
                             timeout=10).stdout.strip() or None
    except OSError:
        rev = None
    row = dict(ts=round(time.time()), git=rev, files=files)
    try:
        with open(out) as f:
            summary = json.load(f)
        rows = summary.get("rows", [])
    except (OSError, ValueError):
        rows = []
    rows.append(row)
    summary = dict(
        note=("one row per `benchmarks.run --summary` invocation; newest "
              "last; headline digests of every BENCH_*.json present"),
        rows=rows[-MAX_ROWS:])
    with open(out, "w") as f:
        json.dump(summary, f, indent=1)
    print(f"# wrote {out} ({len(files)} families, "
          f"{len(summary['rows'])} trajectory rows)")
    return summary


def main() -> None:
    from . import (bench_breakdown, bench_components, bench_compression,
                   bench_entropy, bench_kernels, bench_roofline,
                   bench_search, bench_serve, bench_serve_ann, bench_shard,
                   bench_storage, bench_update)
    print("name,us_per_call,derived")
    t00 = time.time()
    failed = []
    for mod in (bench_entropy, bench_storage, bench_components, bench_search,
                bench_breakdown, bench_update, bench_compression,
                bench_kernels, bench_roofline, bench_serve_ann, bench_serve,
                bench_shard):
        t0 = time.time()
        try:
            mod.main(quiet=True)
        except Exception as e:  # keep the harness going; report the failure
            failed.append(mod.__name__)
            print(f"{mod.__name__},0.0,ERROR:{type(e).__name__}:{e}")
            traceback.print_exc(file=sys.stderr)
        print(f"# {mod.__name__} done in {time.time()-t0:.1f}s",
              file=sys.stderr)
    print(f"# total {time.time()-t00:.1f}s", file=sys.stderr)
    summarize()
    if failed:
        sys.exit(f"# {len(failed)} benchmark(s) failed: {', '.join(failed)}")


if __name__ == '__main__':
    if "--summary" in sys.argv[1:]:
        summarize()
    else:
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        main()
