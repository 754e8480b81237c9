"""Serving-path throughput: QPS vs batch size vs shard count.

The tentpole measurement for `repro/serve/ann.py`: a fixed query stream is
served through `BatchedSearcher` at several pad-and-bucket sizes over 1 and
2 shards, plus the legacy vmapped formulation as the baseline the
hand-batched loop replaces. Rows:

    serve/s{S}_b{B}   us/query   qps;recall@10;graph_ios;cache_hits;...
    serve/vmapped_b{B}            the vmap-of-while_loop baseline
    serve/headline                B=max vs B=1 amortization per shard count

Env: REPRO_BENCH_SERVE_N rescales the corpus (default 2048).
`--smoke` (CLI) shrinks everything to a ~30 s run for `make bench-smoke`.
"""
import argparse
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.distributed.sharded_index import build_sharded_index
from repro.core.index import recall_at_k
from repro.core.search.beam import SearchParams, search_vmapped
from repro.data.synthetic import ground_truth, make_queries, make_vector_dataset
from repro.serve.ann import BatchedSearcher, ServeConfig

from .common import csv

BATCHES = (1, 8, 32)
SHARDS = (1, 2)


def _unshard(sharded):
    """ShardedIndex with S=1 -> the underlying DeviceIndex (named fields:
    ShardedIndex also carries row_ids, which DeviceIndex does not)."""
    from repro.core.search.beam import DeviceIndex
    return DeviceIndex(neighbors=sharded.neighbors[0],
                       counts=sharded.counts[0],
                       ef_slots=sharded.ef_slots[0],
                       pq_codes=sharded.pq_codes[0],
                       pq_centroids=sharded.pq_centroids[0],
                       vectors=sharded.vectors[0],
                       medoid=sharded.medoid[0])


def _bench_point(index, per, queries, gt, p, bucket, reps):
    # QPS is measured with accounting off (raw device path + admission),
    # so it is apples-to-apples with the vmapped baseline; the I/O-model
    # columns come from a separate accounted pass on a FRESH searcher, so
    # they are the cold-cache traversal cost, not warm steady state.
    searcher = BatchedSearcher(index, p,
                               ServeConfig(buckets=(bucket,),
                                           account_io=False),
                               shard_size=per)
    searcher.search(queries[:bucket])            # warm the jit cache
    t0 = time.perf_counter()
    for _ in range(reps):
        ids, dists, _ = searcher.search(queries)
    dt = time.perf_counter() - t0
    n_served = reps * len(queries)
    rec = recall_at_k(ids, gt, min(p.k, gt.shape[1]))
    acct = BatchedSearcher(index, p, ServeConfig(buckets=(bucket,)),
                           shard_size=per)
    _, _, rep = acct.search(queries)
    return dict(us=dt * 1e6 / n_served, qps=n_served / dt, recall=rec,
                report=rep)


def main(quiet=False, n=None, reps=2, n_queries=64, batches=BATCHES,
         shards=SHARDS):
    n = n or int(os.environ.get("REPRO_BENCH_SERVE_N", 2048))
    dim, r, pq_m = 32, 16, 4
    vecs = make_vector_dataset("sift-like", n, dim, seed=0).astype(np.float32)
    queries = make_queries("sift-like", n_queries, dim).astype(np.float32)
    gt = ground_truth(vecs, queries, k=10)

    t0 = time.time()
    indexes = {s: build_sharded_index(vecs, s, r=r, l_build=32, pq_m=pq_m)
               for s in shards}
    if not quiet:
        print(f"# built {len(shards)} index layouts over n={n} "
              f"in {time.time()-t0:.1f}s")

    out = {}
    for s in shards:
        index, per = indexes[s]
        p = SearchParams(l_size=48, beam_width=4, k=10, rerank_batch=10,
                         r_max=r, universe=per, max_iters=128)
        for b in batches:
            pt = _bench_point(index, per, queries, gt, p, b, reps)
            rep = pt["report"]
            csv(f"serve/s{s}_b{b}", pt["us"],
                f"qps={pt['qps']:.0f};recall={pt['recall']:.3f};"
                f"cold_graph_ios={rep.graph_ios};"
                f"cold_cache_hits={rep.cache_hits};"
                f"cold_io_rounds={rep.io_rounds};"
                f"cold_lat_model_us={rep.modeled_latency_us:.0f}")
            out[(s, b)] = pt

    # Baseline: the vmapped per-query formulation at the largest bucket
    # (single-device comparison — only meaningful when shards=1 is swept).
    if 1 in shards:
        index1 = _unshard(indexes[1][0])
        p1 = SearchParams(l_size=48, beam_width=4, k=10, rerank_batch=10,
                          r_max=r, universe=indexes[1][1], max_iters=128)
        b = max(batches)
        q = np.asarray(queries[:b])
        search_vmapped(index1, q, p1)            # compile
        t0 = time.perf_counter()
        for _ in range(reps):
            search_vmapped(index1, q, p1)[0].block_until_ready()
        us = (time.perf_counter() - t0) * 1e6 / (reps * b)
        batched_us = out[(1, b)]["us"]
        csv(f"serve/vmapped_b{b}", us,
            f"qps={1e6/us:.0f};batched_speedup={us/batched_us:.2f}x")

    for s in shards:
        lo, hi = out[(s, min(batches))], out[(s, max(batches))]
        csv("serve/headline", 0.0,
            f"s{s}:qps_b{max(batches)}={hi['qps']:.0f}"
            f"_vs_b{min(batches)}={lo['qps']:.0f}"
            f"_gain={hi['qps']/lo['qps']:.2f}x")
    return out


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--reps", type=int, default=2)
    ap.add_argument("--batch", default="1,8,32",
                    help="comma-separated bucket sizes to sweep")
    ap.add_argument("--shards", default="1,2")
    ap.add_argument("--smoke", action="store_true",
                    help="~30s run: n=768, 32 queries, 1 rep")
    args = ap.parse_args()
    kw = dict(n=args.n, reps=args.reps, n_queries=args.queries,
              batches=tuple(int(x) for x in args.batch.split(",")),
              shards=tuple(int(x) for x in args.shards.split(",")))
    if args.smoke:
        kw.update(n=args.n or 768, reps=1, n_queries=32)
    print("name,us_per_call,derived")
    main(**kw)
