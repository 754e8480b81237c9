"""Exp#3/#4 (Fig. 7/8): search throughput & latency vs recall frontier.

Sweeps the candidate list size L for DiskANN, PipeANN, DecoupleVS and
DecoupleVS over a minla-reordered index store, and reports (recall@10,
modeled QPS, modeled mean latency, blocks/hop) per point — the paper's
accuracy/throughput frontier, in I/O-model units. The reorder arm must sit
ON the DecoupleVS frontier (permutation invariance: same ids, same recall)
while touching fewer distinct 4 KiB index blocks per beam hop.

The ``--batch`` axis (also swept by ``main``) pushes the same query set
through the batched device serving path (`repro.serve.ann.BatchedSearcher`)
and reports measured QPS per bucket size — wall-clock units, not I/O-model
units, so it complements rather than replaces the frontier above.

**Pipeline arms (Exp#4 companion, written to ``BENCH_search.json``):** the
same minla-ordered DecoupleVS configuration priced three ways on fresh
stores — ``blocking`` (every stall serial), ``pipelined`` (speculative
multi-hop prefetch + overlap pricing), ``pipelined_coresident`` (prefetch
over the co-residency block packing). Results are bit-identical by
construction (asserted), so recall is pinned equal and the arms differ
ONLY in modeled latency, blocks/hop and prefetch hit-rate.

Env: REPRO_BENCH_SEARCH_OUT overrides the JSON path. ``--smoke`` runs just
the pipeline arms on a query subset (the CI gate: pipelined <= blocking
and coresident < blocking at identical recall).
"""
import argparse
import json
import os
import time

import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core.index import device_index_from_artifacts, recall_at_k
from repro.core.search.beam import SearchParams
from repro.core.search.engine import (EngineConfig, search_colocated,
                                      search_decoupled)
from repro.core.storage.index_store import CompressedIndexStore
from repro.serve.ann import BatchedSearcher, ServeConfig

from .common import CACHE_BYTES, R, csv, reset_io, world

L_SWEEP = (24, 48, 96, 160)
BATCH_SWEEP = (1, 8, 32)

_ORDERED_IX = {}


def _ordered_ix(w):
    """The minla-relabeled index store for a world, built once (the seal
    path computes the ordering; the engine un-maps at the API boundary)."""
    if w["kind"] not in _ORDERED_IX:
        g = w["graph"]
        _ORDERED_IX[w["kind"]] = CompressedIndexStore.from_graph(
            g.adjacency, g.medoid, R, cache_bytes=CACHE_BYTES,
            order="minla")
    return _ORDERED_IX[w["kind"]]


def _frontier(w, system: str):
    pts = []
    for l in L_SWEEP:
        reset_io(w)
        if system == "decouplevs_reorder":
            ix = _ordered_ix(w)
            ix.io.reads = ix.io.read_bytes = 0
            ix.cache.reset_stats()
            ix.cache._d.clear()
        ids_all, stats = [], []
        for q in w["queries"]:
            if system in ("diskann", "pipeann"):
                cfg = EngineConfig(l_size=l, pipelined=system == "pipeann")
                ids, st = search_colocated(w["colo"], w["codes"], w["cb"],
                                           q, cfg)
            else:
                cfg = EngineConfig(l_size=l, latency_aware=True,
                                   compressed=True)
                ix = _ordered_ix(w) if system == "decouplevs_reorder" \
                    else w["comp_ix"]
                ids, st = search_decoupled(ix, w["vs"], w["codes"],
                                           w["cb"], q, cfg)
            ids_all.append(np.pad(ids, (0, 10 - len(ids)),
                                  constant_values=-1))
            stats.append(st)
        lat = float(np.mean([s.latency_us for s in stats]))
        p99 = float(np.percentile([s.latency_us for s in stats], 99))
        rec = recall_at_k(np.stack(ids_all), w["gt"], 10)
        pts.append(dict(l=l, recall=rec, latency_us=lat, p99_us=p99,
                        qps=1e6 / lat,
                        blocks_per_hop=float(
                            np.mean([s.blocks_per_hop for s in stats]))))
    return pts


# (name, EngineConfig overrides, coresident packing) per pipeline arm.
PIPELINE_ARMS = (
    ("blocking", dict(pricing="blocking"), False),
    ("pipelined", dict(pricing="pipelined_overlap", prefetch_depth=8), False),
    ("pipelined_coresident",
     dict(pricing="pipelined_overlap", prefetch_depth=8), True),
)


def _pipeline_arms(w, l: int = 96, nq: int = 0, quiet: bool = False):
    """Blocking vs pipelined vs pipelined+coresident on FRESH minla-ordered
    stores (cold caches per arm, same queries). Returns the per-arm dict;
    asserts bit-identical ids across arms (recall pinned equal) and the
    latency ordering the overlap model guarantees."""
    g = w["graph"]
    queries = w["queries"][:nq] if nq else w["queries"]
    gt = w["gt"][:len(queries)]
    out, ids_ref = {}, None
    for name, overrides, coresident in PIPELINE_ARMS:
        ix = CompressedIndexStore.from_graph(
            g.adjacency, g.medoid, R, cache_bytes=CACHE_BYTES,
            order="minla", coresident=coresident)
        cfg = EngineConfig(l_size=l, latency_aware=True, compressed=True,
                           **overrides)
        ids_all, stats = [], []
        for q in queries:
            ids, st = search_decoupled(ix, w["vs"], w["codes"], w["cb"],
                                       q, cfg)
            ids_all.append(np.pad(ids, (0, 10 - len(ids)),
                                  constant_values=-1))
            stats.append(st)
        ids_arr = np.stack(ids_all)
        if ids_ref is None:
            ids_ref = ids_arr
        else:
            assert np.array_equal(ids_arr, ids_ref), \
                f"{name}: prefetch/packing changed results"
        lats = [s.latency_us for s in stats]
        issued = sum(s.prefetch_issued for s in stats)
        hits = sum(s.prefetch_hits for s in stats)
        out[name] = dict(
            l=l,
            recall=recall_at_k(ids_arr, gt, 10),
            latency_us=float(np.mean(lats)),
            p50_us=float(np.percentile(lats, 50)),
            p99_us=float(np.percentile(lats, 99)),
            blocks_per_hop=float(np.mean([s.blocks_per_hop
                                          for s in stats])),
            io_rounds=int(sum(s.io_rounds for s in stats)),
            covered_rounds=int(sum(s.covered_rounds for s in stats)),
            prefetch_issued=int(issued),
            prefetch_hits=int(hits),
            prefetch_wasted=int(sum(s.prefetch_wasted for s in stats)),
            prefetch_hit_rate=hits / issued if issued else 0.0,
            overlap_saved_us=float(sum(s.overlap_saved_us for s in stats)),
            sparse_index_bytes=int(ix.sparse_index_bytes),
            component_prefetch=ix.blocks.prefetch_stats())
        if not quiet:
            a = out[name]
            csv(f"exp4/pipeline_{name}", a["latency_us"],
                f"recall={a['recall']:.3f};p50={a['p50_us']:.0f};"
                f"bph={a['blocks_per_hop']:.2f};"
                f"pf_hit_rate={a['prefetch_hit_rate']:.2f};"
                f"covered={a['covered_rounds']};"
                f"wasted={a['prefetch_wasted']}")
    # The overlap model's guarantee (io_rounds_blocking = io_rounds +
    # covered_rounds on an identical traversal): pipelined can never price
    # above blocking; co-residency must win outright at this scale.
    assert out["pipelined"]["latency_us"] <= out["blocking"]["latency_us"]
    assert out["pipelined_coresident"]["latency_us"] \
        < out["blocking"]["latency_us"]
    return out


def _write_search_json(w, arms: dict, l: int, nq: int) -> str:
    doc = dict(
        n=len(w["vecs"]), l=l, n_queries=nq or len(w["queries"]),
        arms=arms,
        suite=dict(
            equal_recall=True,      # asserted: bit-identical ids per arm
            pipelined_leq_blocking=bool(
                arms["pipelined"]["latency_us"]
                <= arms["blocking"]["latency_us"]),
            coresident_lt_blocking=bool(
                arms["pipelined_coresident"]["latency_us"]
                < arms["blocking"]["latency_us"]),
            prefetch_hit_rate=arms["pipelined"]["prefetch_hit_rate"],
            coresident_hit_rate=arms["pipelined_coresident"][
                "prefetch_hit_rate"]))
    path = os.environ.get("REPRO_BENCH_SEARCH_OUT", "BENCH_search.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"# wrote {path} (blocking {arms['blocking']['latency_us']:.0f}us "
          f"-> pipelined {arms['pipelined']['latency_us']:.0f}us -> "
          f"coresident {arms['pipelined_coresident']['latency_us']:.0f}us "
          f"at recall={arms['blocking']['recall']:.3f})")
    return path


def _batched_serving(w, batches):
    """Measured QPS of the batched device path per bucket size (exp#3's
    serving companion: same corpus/queries, wall-clock units)."""
    vecs = w["vecs"].astype(np.float32)
    index = device_index_from_artifacts(vecs, w["graph"], w["cb"], w["codes"])
    p = SearchParams(l_size=48, beam_width=4, k=10, rerank_batch=10,
                     r_max=w["graph"].r, universe=len(vecs), max_iters=128)
    queries = np.asarray(w["queries"], np.float32)
    for b in batches:
        searcher = BatchedSearcher(index, p,
                                   ServeConfig(buckets=(b,),
                                               account_io=False))
        searcher.search(queries[:b])             # warm the jit cache
        t0 = time.perf_counter()
        ids, _, _ = searcher.search(queries)
        us = (time.perf_counter() - t0) * 1e6 / len(queries)
        rec = recall_at_k(ids, w["gt"], 10)
        acct = BatchedSearcher(index, p, ServeConfig(buckets=(b,)))
        _, _, rep = acct.search(queries)         # cold-cache I/O columns
        csv(f"exp3/serve_b{b}", us,
            f"qps={1e6/us:.0f};recall={rec:.3f};"
            f"cold_graph_ios={rep.graph_ios};"
            f"cold_cache_hits={rep.cache_hits}")


def main(quiet=False, batches=BATCH_SWEEP, smoke=False):
    w = world("sift-like")
    if smoke:
        arms = _pipeline_arms(w, l=48, nq=16, quiet=quiet)
        _write_search_json(w, arms, l=48, nq=16)
        return arms
    out = {}
    for system in ("diskann", "pipeann", "decouplevs",
                   "decouplevs_reorder"):
        t0 = time.time()
        pts = _frontier(w, system)
        us = (time.time() - t0) * 1e6 / (len(L_SWEEP) * len(w["queries"]))
        frontier = ";".join(f"L{p['l']}:r={p['recall']:.3f}:"
                            f"qps={p['qps']:.0f}:p99={p['p99_us']:.0f}:"
                            f"bph={p['blocks_per_hop']:.2f}"
                            for p in pts)
        csv(f"exp3/{system}", us, frontier)
        out[system] = pts
    # The reorder arm's contract: equal recall at every L (permutation
    # invariance through the engine) with fewer index blocks per hop.
    for base, re_ in zip(out["decouplevs"], out["decouplevs_reorder"]):
        assert re_["recall"] == base["recall"], \
            (re_["l"], re_["recall"], base["recall"])
    mean_base = float(np.mean([p["blocks_per_hop"]
                               for p in out["decouplevs"]]))
    mean_re = float(np.mean([p["blocks_per_hop"]
                             for p in out["decouplevs_reorder"]]))
    csv("exp3/reorder_locality", 0.0,
        f"blocks_per_hop={mean_base:.2f}->{mean_re:.2f};"
        f"equal_recall_at_all_L=true")
    # Exp#9 (appendix): P99 tail latency at the mid-recall operating point
    for system, pts in out.items():
        mid = pts[len(pts) // 2]
        csv(f"exp9/{system}", 0.0,
            f"L{mid['l']}:recall={mid['recall']:.3f};"
            f"p99_us={mid['p99_us']:.0f};mean_us={mid['latency_us']:.0f};"
            f"tail_ratio={mid['p99_us']/mid['latency_us']:.2f}")
    # Exp#3 headline: throughput gain at matched recall (best common point)
    best_dvs = max(out["decouplevs"], key=lambda p: p["recall"])
    match_dk = min(out["diskann"],
                   key=lambda p: abs(p["recall"] - best_dvs["recall"]))
    csv("exp3/headline", 0.0,
        f"dvs_vs_diskann_qps_gain="
        f"{best_dvs['qps']/match_dk['qps']:.2f}x_at_recall~"
        f"{best_dvs['recall']:.3f}")
    arms = _pipeline_arms(w, l=96, quiet=quiet)
    _write_search_json(w, arms, l=96, nq=0)
    _batched_serving(w, batches)
    return out


if __name__ == "__main__":
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", default="1,8,32",
                    help="comma-separated serving bucket sizes to sweep")
    ap.add_argument("--smoke", action="store_true",
                    help="pipeline arms only, query subset (CI gate)")
    args = ap.parse_args()
    main(batches=tuple(int(x) for x in args.batch.split(",")),
         smoke=args.smoke)
