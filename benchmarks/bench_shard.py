"""Mesh-scale sharded serving: QPS-vs-shards scaling under the I/O model.

The tentpole measurement for the sharded serving tier (ROADMAP item 3): a
clustered corpus is partitioned over S ∈ {1, 8, 16, 32} shards (balanced
k-means partitions + a replicated centroid router) and a fixed query stream
is served through ``BatchedSearcher`` with accounting on. Modeled QPS is
the open-loop critical path: the busiest shard's summed per-query latency
plus the per-query hierarchical merge price
(:func:`~repro.core.search.engine.shard_merge_cost_us`).

Arms per S:
  shard/s{S}_full     route_frac=1.0 (every query fans out to every shard)
  shard/s{S}_routed   route_frac=ROUTE_FRAC (selective SPANN-style routing)
Plus, at S=8:
  shard/route_sweep   recall-vs-fanout curve over route_frac
  shard/failed        one shard dropped (graceful degradation arm)
  shard/merge_rows    hier vs flat gathered rows (K·log2 S vs K·S)

Suite gates (CI smoke runs this with a small corpus):
  - scaling efficiency at S=8 (routed QPS vs 8x the S=1 QPS) >= floor
  - hier merge rows <= K·log2(S)·n_nodes at every S (vs K·S flat)
  - routed recall@10 within RECALL_TOL of full fan-out at ROUTE_FRAC
  - route_frac=1.0 through the router is BIT-IDENTICAL to no router
  - failed-shard arm completes and stays within FAILED_RECALL_DROP

JSON: BENCH_shard.json (env REPRO_BENCH_SHARD_OUT overrides).
Env: REPRO_BENCH_SHARD_N rescales the corpus (default 4096).
"""
import argparse
import json
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.distributed.sharded_index import (build_router,
                                                  build_sharded_index,
                                                  merge_comm_rows)
from repro.core.index import recall_at_k
from repro.core.search.beam import SearchParams
from repro.core.search.engine import shard_merge_cost_us
from repro.data.synthetic import ground_truth, make_vector_dataset
from repro.serve.ann import BatchedSearcher, ServeConfig

from .common import csv

SHARDS = (1, 8, 16, 32)
ROUTE_FRAC = 0.25            # default selective fan-out (2/8, 4/16, 8/32)
ROUTE_SWEEP = (0.125, 0.25, 0.5, 1.0)
GATE_SCALING_EFFICIENCY_S8 = 0.30   # routed QPS_8 / (8 * QPS_1)
RECALL_TOL = 0.01            # routed recall@10 within this of full fan-out
FAILED_RECALL_DROP = 0.20    # 1-of-8 shards down: recall drop bound


def _modeled_qps(report, n_queries: int, k: int, n_shards: int,
                 merge: str = "hier") -> float:
    """Open-loop modeled QPS: shards serve in parallel, so throughput is
    bound by the busiest shard's summed modeled latency, plus the per-query
    cross-shard merge at the engine's comm price."""
    busy = max(report.shard_busy_us) if report.shard_busy_us else 0.0
    merge_us = n_queries * shard_merge_cost_us(k, [n_shards], mode=merge) \
        if n_shards > 1 else 0.0
    return n_queries * 1e6 / max(busy + merge_us, 1e-9)


def _serve(index, router, p, queries, route_frac, failed=None,
           buckets=(32,)):
    searcher = BatchedSearcher(
        index, p, ServeConfig(buckets=buckets, route_frac=route_frac),
        router=router)
    ids, dists, rep = searcher.search(queries, failed_shards=failed)
    return np.asarray(ids), np.asarray(dists), rep


def main(quiet=False, n=None, n_queries=64, shards=SHARDS):
    n = n or int(os.environ.get("REPRO_BENCH_SHARD_N", 4096))
    dim, r, pq_m, k = 32, 16, 4, 10
    vecs = make_vector_dataset("cluster-like", n, dim, seed=0)
    # Queries perturb held-out base rows: same cluster structure the router
    # scores (make_queries would draw FRESH centers — a different mixture).
    rng = np.random.default_rng(1)
    qid = rng.choice(n, size=n_queries, replace=False)
    queries = vecs[qid] + rng.normal(0, 0.02, size=(n_queries, dim)) \
        .astype(np.float32)
    gt = ground_truth(vecs, queries, k=k)

    t0 = time.time()
    worlds = {}
    for s in shards:
        index, per = build_sharded_index(vecs, s, r=r, l_build=32,
                                         pq_m=pq_m, partition="cluster")
        worlds[s] = (index, per,
                     build_router(index, c=32) if s > 1 else None)
    if not quiet:
        print(f"# built {len(shards)} clustered shard layouts over n={n} "
              f"in {time.time()-t0:.1f}s")

    out = dict(world=dict(n=n, dim=dim, r=r, k=k, n_queries=n_queries,
                          partition="cluster", route_frac=ROUTE_FRAC),
               scaling={}, merge_rows={}, route_sweep={})
    qps = {}
    for s in shards:
        index, per, router = worlds[s]
        p = SearchParams(l_size=48, beam_width=4, k=k, rerank_batch=10,
                         r_max=r, universe=per, max_iters=128)
        ids_f, _, rep_f = _serve(index, router, p, queries, 1.0)
        rec_f = recall_at_k(ids_f, gt, k)
        qps_f = _modeled_qps(rep_f, n_queries, k, s)
        row = dict(full=dict(recall=rec_f, qps=qps_f,
                             busy_us=rep_f.shard_busy_us,
                             fanout_frac=rep_f.fanout_frac))
        if s > 1:
            ids_r, _, rep_r = _serve(index, router, p, queries, ROUTE_FRAC)
            rec_r = recall_at_k(ids_r, gt, k)
            qps_r = _modeled_qps(rep_r, n_queries, k, s)
            row["routed"] = dict(recall=rec_r, qps=qps_r,
                                 busy_us=rep_r.shard_busy_us,
                                 fanout_frac=rep_r.fanout_frac,
                                 routed_rows=rep_r.routed_rows)
            qps[s] = qps_r
        else:
            qps[s] = qps_f
        out["scaling"][s] = row
        out["merge_rows"][s] = dict(
            hier=merge_comm_rows(k, [s], "hier"),
            flat=merge_comm_rows(k, [s], "flat"),
            bound=int(k * max(1.0, np.ceil(np.log2(max(s, 2))))))
        derived = f"qps_full={qps_f:.0f};recall_full={rec_f:.3f}"
        if s > 1:
            derived += (f";qps_routed={qps[s]:.0f};recall_routed="
                        f"{row['routed']['recall']:.3f};fanout="
                        f"{row['routed']['fanout_frac']:.3f}")
        csv(f"shard/s{s}", 1e6 / qps[s], derived)

    # ---- routing quality at S=8: sweep + bit-identity + failed shard ----
    s8 = 8 if 8 in shards else max(s for s in shards if s > 1)
    index, per, router = worlds[s8]
    p = SearchParams(l_size=48, beam_width=4, k=k, rerank_batch=10,
                     r_max=r, universe=per, max_iters=128)
    for frac in ROUTE_SWEEP:
        ids_x, _, rep_x = _serve(index, router, p, queries, frac)
        out["route_sweep"][frac] = dict(
            recall=recall_at_k(ids_x, gt, k),
            qps=_modeled_qps(rep_x, n_queries, k, s8),
            fanout_frac=rep_x.fanout_frac)
        csv(f"shard/route_sweep_f{frac}",
            1e6 / out["route_sweep"][frac]["qps"],
            f"recall={out['route_sweep'][frac]['recall']:.3f};"
            f"fanout={out['route_sweep'][frac]['fanout_frac']:.3f}")
    ids_nr, d_nr, _ = _serve(index, None, p, queries, 1.0)
    ids_rt, d_rt, _ = _serve(index, router, p, queries, 1.0)
    bit_identical = bool(np.array_equal(ids_nr, ids_rt)
                         and np.array_equal(d_nr, d_rt))
    ids_fl, _, rep_fl = _serve(index, router, p, queries, 1.0, failed=[0])
    rec_failed = recall_at_k(ids_fl, gt, k)
    rec_full8 = out["scaling"][s8]["full"]["recall"]
    out["failed_shard"] = dict(shard=0, recall=rec_failed,
                               recall_full=rec_full8,
                               drop=rec_full8 - rec_failed,
                               reported=rep_fl.failed_shards)
    csv("shard/failed", 0.0,
        f"recall={rec_failed:.3f};drop={rec_full8-rec_failed:.3f}")

    # ------------------------------------------------------------- gates
    eff = qps[s8] / (s8 * qps[1]) if 1 in shards else float("nan")
    hier_ok = all(m["hier"] <= max(m["bound"], k)
                  and (s == 1 or m["hier"] <= m["flat"])
                  for s, m in out["merge_rows"].items())
    rec_routed8 = out["scaling"][s8].get("routed", {}).get(
        "recall", rec_full8)
    out["suite"] = dict(
        scaling_efficiency_s8=float(eff),
        gate_scaling_efficiency_s8=GATE_SCALING_EFFICIENCY_S8,
        qps={str(s): float(q) for s, q in qps.items()},
        hier_rows_leq_bound=bool(hier_ok),
        routed_recall_delta=float(rec_full8 - rec_routed8),
        recall_tol=RECALL_TOL,
        router_full_frac_bit_identical=bit_identical,
        failed_shard_drop=float(out["failed_shard"]["drop"]),
        failed_shard_drop_bound=FAILED_RECALL_DROP,
        passed=bool((not np.isfinite(eff)
                     or eff >= GATE_SCALING_EFFICIENCY_S8)
                    and hier_ok
                    and rec_full8 - rec_routed8 <= RECALL_TOL
                    and bit_identical
                    and out["failed_shard"]["drop"]
                    <= FAILED_RECALL_DROP))
    csv("shard/headline", 0.0,
        f"eff_s{s8}={eff:.2f};gate>={GATE_SCALING_EFFICIENCY_S8};"
        f"recall_delta={out['suite']['routed_recall_delta']:.3f};"
        f"bit_identical={bit_identical};passed={out['suite']['passed']}")
    path = os.environ.get("REPRO_BENCH_SHARD_OUT", "BENCH_shard.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
    if not quiet:
        print(f"# wrote {path} (scaling efficiency s{s8} = {eff:.2f}, "
              f"passed={out['suite']['passed']})")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--shards", default="1,8,16,32")
    ap.add_argument("--smoke", action="store_true",
                    help="small corpus, S=(1,8) only")
    args = ap.parse_args()
    kw = dict(n=args.n, n_queries=args.queries,
              shards=tuple(int(x) for x in args.shards.split(",")))
    if args.smoke:
        kw.update(n=args.n or 1024, n_queries=32, shards=(1, 8))
    print("name,us_per_call,derived")
    main(**kw)
