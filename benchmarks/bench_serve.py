"""Admission-tier SLO benchmark: open-loop traces through the simulated
clock (`repro/serve/admission.py`).

Two trace shapes at the SAME mean offered load — Poisson and on/off bursty
— are drained through `AdmissionQueue` over a two-tenant `BatchedSearcher`
(hot tenant rate-capped with a cache quota floor, cold tenant unthrottled).
Every latency number is MODELED (simulated clock + the engine's
T_IO/T_PQ/T_EX/T_DEC pricing), so the whole artifact is deterministic for
the pinned seeds: rows reproduce bit-for-bit across machines.

Rows:
    serve/adm_poisson   p99_us   qps;p50;p95;p99;misses;...
    serve/adm_bursty    p99_us   (same, bursty trace)
    serve/adm_headline  ratio    bursty p99 over poisson p99 + gate

JSON: BENCH_serve.json (env REPRO_BENCH_SERVE_OUT overrides) with per-trace
latency percentiles, QPS, deadline misses, per-tenant stats, and a
``suite`` block: ``bursty_over_poisson_p99`` must stay within the declared
``gate_bursty_over_poisson_p99`` multiple — the regression gate CI's
bench-serve smoke asserts.

Env: REPRO_BENCH_SERVE_ADM_N (corpus, default 2048),
REPRO_BENCH_SERVE_ADM_REQS (requests per trace, default 512).
``--smoke`` shrinks both for the CI step (~40 s).
"""
import argparse
import json
import os

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.index import build_device_index
from repro.core.search.beam import SearchParams
from repro.data.synthetic import make_queries, make_vector_dataset
from repro.serve.admission import (AdmissionConfig, AdmissionQueue,
                                   TenantConfig, bursty_trace,
                                   calibrate_service_model, poisson_trace)
from repro.serve.ann import BatchedSearcher, ServeConfig

from .common import csv

MAX_BATCH = 32
BUCKETS = (1, 8, 32)
# Declared SLO gate: bursty tail within this multiple of the Poisson tail
# at the same mean rate. Measured (deterministic, pinned seeds): ~1.3-1.9x
# across the smoke and full sizes; 3.0 is the regression alarm, not the
# target.
GATE_BURSTY_OVER_POISSON_P99 = 3.0


def _world(n, dim=32):
    vecs = make_vector_dataset("prop-like", n=n, dim=dim,
                               seed=0).astype(np.float32)
    index, _, _ = build_device_index(vecs, r=16, l_build=32, pq_m=8, seed=0)
    queries = make_queries("prop-like", 64, dim).astype(np.float32)
    p = SearchParams(l_size=32, beam_width=4, k=10, rerank_batch=8,
                     r_max=16, universe=n, max_iters=64)
    return index, queries, p


def _searcher(index, p, tenants):
    s = BatchedSearcher(index, p, ServeConfig(buckets=BUCKETS,
                                              shared_budget=True))
    for name, tc in tenants.items():
        s.register_tenant(name, floor_bytes=tc.cache_floor_bytes)
    return s


def _drain(index, p, model, tenants, trace):
    q = AdmissionQueue(_searcher(index, p, tenants), model,
                       AdmissionConfig(max_batch=MAX_BATCH), tenants=tenants)
    served, report = q.run(trace)
    reasons = {}
    for rec in report.batches:
        reasons[rec.reason] = reasons.get(rec.reason, 0) + 1
    return dict(
        n_requests=report.n_requests, n_batches=report.n_batches,
        qps=report.qps, makespan_us=report.makespan_us,
        deadline_misses=report.deadline_misses,
        miss_rate=report.deadline_misses / max(1, report.n_requests),
        latency_us=report.latency, cut_reasons=reasons,
        mean_batch=report.n_requests / max(1, report.n_batches),
        tenants=report.tenant_stats)


def main(quiet: bool = False, smoke: bool = False):
    n = int(os.environ.get("REPRO_BENCH_SERVE_ADM_N",
                           400 if smoke else 2048))
    n_reqs = int(os.environ.get("REPRO_BENCH_SERVE_ADM_REQS",
                                160 if smoke else 512))
    index, queries, p = _world(n)
    # Price the service model from an accounted probe on a scratch searcher
    # (cold cache) — the slack formula's raw material.
    model = calibrate_service_model(
        BatchedSearcher(index, p, ServeConfig(buckets=(MAX_BATCH,))),
        queries[:MAX_BATCH])
    # Offer ~60% of the modeled full-batch capacity; deadline = 4x the
    # full-batch service time (tight enough that bursts cause misses).
    capacity_qps = MAX_BATCH / model.service_us(MAX_BATCH) * 1e6
    rate = 0.6 * capacity_qps
    deadline_us = 4.0 * model.service_us(MAX_BATCH)
    # The hot tenant's quota (0.5x total rate) exceeds its MEAN offered
    # share (0.4x) but not its burst peaks: under Poisson the bucket rarely
    # bites, under the bursty trace the ON phases exceed the quota and the
    # deferred queue (and its tail latency) is the isolation cost.
    tenants = {"hot": TenantConfig(rate_qps=0.5 * rate, burst=8.0,
                                   cache_floor_bytes=64 << 10),
               "cold": TenantConfig()}
    trace_kw = dict(rate_qps=rate, n=n_reqs, tenants=tuple(tenants),
                    weights=(0.4, 0.6), deadline_us=deadline_us, seed=0)
    out = dict(
        world=dict(n=n, dim=32, buckets=list(BUCKETS), max_batch=MAX_BATCH),
        model=dict(per_query_us=model.per_query_us, base_us=model.base_us,
                   capacity_qps=capacity_qps),
        offered=dict(rate_qps=rate, deadline_us=deadline_us,
                     n_requests=n_reqs,
                     tenants={t: dict(rate_qps=tc.rate_qps, burst=tc.burst,
                                      cache_floor_bytes=tc.cache_floor_bytes)
                              for t, tc in tenants.items()}),
        traces={})
    out["traces"]["poisson"] = _drain(
        index, p, model, tenants, poisson_trace(queries, **trace_kw))
    out["traces"]["bursty"] = _drain(
        index, p, model, tenants,
        bursty_trace(queries, burst_factor=8.0, duty=0.2,
                     period_us=16.0 * model.service_us(MAX_BATCH),
                     **trace_kw))
    for kind, r in out["traces"].items():
        lat = r["latency_us"]
        csv(f"serve/adm_{kind}", lat["p99"],
            f"qps={r['qps']:.0f};p50={lat['p50']:.0f};"
            f"p95={lat['p95']:.0f};p99={lat['p99']:.0f};"
            f"miss_rate={100*r['miss_rate']:.1f}%;"
            f"mean_batch={r['mean_batch']:.1f};"
            f"cuts={r['cut_reasons']};"
            f"hot_throttle_us={r['tenants']['hot']['throttle_us_mean']:.0f}")
    ratio = (out["traces"]["bursty"]["latency_us"]["p99"]
             / max(1e-9, out["traces"]["poisson"]["latency_us"]["p99"]))
    out["suite"] = dict(
        bursty_over_poisson_p99=float(ratio),
        gate_bursty_over_poisson_p99=GATE_BURSTY_OVER_POISSON_P99,
        poisson_p99_us=out["traces"]["poisson"]["latency_us"]["p99"],
        bursty_p99_us=out["traces"]["bursty"]["latency_us"]["p99"],
        passed=bool(ratio <= GATE_BURSTY_OVER_POISSON_P99))
    csv("serve/adm_headline", ratio,
        f"bursty_p99/poisson_p99={ratio:.2f}"
        f";gate<={GATE_BURSTY_OVER_POISSON_P99};"
        f"passed={out['suite']['passed']}")
    path = os.environ.get("REPRO_BENCH_SERVE_OUT", "BENCH_serve.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    if not quiet:
        print(f"# wrote {path} (bursty/poisson p99 = {ratio:.2f}, "
              f"gate {GATE_BURSTY_OVER_POISSON_P99})")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small world for the CI gate (~40 s)")
    args = ap.parse_args()
    main(smoke=args.smoke)
