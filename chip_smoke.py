"""Bring-up check: drive the served ANN search path once on a TPU.

    python chip_smoke.py [--seed N] [--chips 4]

One process, no children. It refuses to run unless JAX's first device is a
TPU: there is no CPU or interpreter fallback. Everything is generated from
``--seed``. With no option it runs three phases on one chip:

  serve     SIFT-like uint8 base (d=128), ``build_device_index``, 64 queries
            through ``BatchedSearcher`` at the production config's search
            widths (L=200, W=4, K=10, B=10), once with every kernel resolved
            by ``auto`` (compiled Pallas) and once with the jnp oracle and
            the unfused hop. Ids must agree, and recall@10 against float64
            ground truth must reach what the oracle gives on the CPU backend
            of this same process. Also loads the base back through the
            vector store's byteplane path.
  update    one ``StreamingIndex`` round on the serve index (insert,
            delete, merge, publish) served through ``BatchedSearcher`` on
            the ``SnapshotHandle``.
  capacity  the production widths (d=128, R=128, PQ m=32) at n = 2^20 rows
            of device state; Pallas ids must equal oracle ids and every
            returned distance must equal float64 L2 of its id.

``--chips 4`` runs only the sharded phase: a 4-shard index placed one shard
per chip, searched by the ``shard_map`` program with the hierarchical merge
and by the serial per-shard path; their ids must be identical.

Every line but the last is a log line. The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``, printed only when every check of
every phase passed; otherwise the script exits non-zero without it. Times
printed are one run's wall clock, compile included, and are not speed
measurements.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

N_SERVE, R_SERVE, L_BUILD = 2048, 32, 64
N_QUERIES = 64
N_CAPACITY = 1 << 20
N_UPDATE = 64
SHARDS = 4


class CheckFailed(AssertionError):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, what: str) -> None:
    log(f"  [{'pass' if ok else 'FAIL'}] {what}")
    if not ok:
        raise CheckFailed(what)


def _params(cfg, r: int, universe: int, **kw):
    from repro.core.search.beam import SearchParams
    return SearchParams(l_size=cfg.l_size, beam_width=cfg.beam_width,
                        k=cfg.k, rerank_batch=cfg.rerank_batch, r_max=r,
                        universe=universe, **kw)


def _ref():
    from repro.kernels.dispatch import KernelConfig
    return KernelConfig("ref", "ref", "ref", "ref", "off")


def _serve(searcher, queries):
    t0 = time.perf_counter()
    ids, dists, _ = searcher.search(queries)
    return ids, dists, time.perf_counter() - t0


def _exact_l2(base, queries, ids) -> np.ndarray:
    """float64 squared L2 of each returned id (inf for -1)."""
    x = base[np.clip(ids, 0, len(base) - 1)].astype(np.float64)
    d = ((x - queries[:, None, :].astype(np.float64)) ** 2).sum(-1)
    return np.where(ids >= 0, d, np.inf)


def serve_phase(seed: int, fast, n: int = N_SERVE, r: int = R_SERVE,
                n_queries: int = N_QUERIES) -> dict:
    """Build, serve under ``fast`` and under the oracle, compare."""
    import jax
    from repro.configs.decouplevs_ann import CONFIG
    from repro.core.index import build_device_index, recall_at_k
    from repro.core.storage.vector_store import (DecoupledVectorStore,
                                                 StoreConfig)
    from repro.data.synthetic import (ground_truth, make_queries,
                                      make_vector_dataset)
    from repro.serve.ann import BatchedSearcher

    # The update phase inserts the last N_UPDATE rows of the same draw.
    rows = make_vector_dataset("sift-like", n + N_UPDATE, CONFIG.dim,
                               seed=seed)
    base = rows[:n]
    queries = make_queries("sift-like", n_queries, CONFIG.dim,
                           seed=seed).astype(np.float32)
    t0 = time.perf_counter()
    index, graph, cb = build_device_index(base, r=r, l_build=L_BUILD,
                                          pq_m=CONFIG.pq_m, seed=seed)
    log(f"serve: built n={n} d={CONFIG.dim} uint8, R={r}, L_build={L_BUILD},"
        f" pq_m={CONFIG.pq_m} in {time.perf_counter() - t0:.1f}s (cut from "
        f"the config's n=1e9, R={CONFIG.r}: the pure-Python build_vamana "
        f"takes ~23 ms/vertex at R=32, ~59 ms at R=64)")
    gt = ground_truth(base, queries, k=CONFIG.k)
    p = _params(CONFIG, r, n)
    pal = BatchedSearcher(index, p._replace(kernels=fast))
    log(f"serve: resolved kernels {tuple(pal.p.kernels)} "
        f"(fields {pal.p.kernels._fields})")
    ids_p, d_p, t_p = _serve(pal, queries)
    ids_r, d_r, t_r = _serve(BatchedSearcher(index, p._replace(
        kernels=_ref())), queries)
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ids_c, _, t_c = _serve(BatchedSearcher(jax.device_put(index, cpu),
                                               p._replace(kernels=_ref())),
                               queries)
    rec_p = recall_at_k(ids_p, gt, CONFIG.k)
    rec_c = recall_at_k(ids_c, gt, CONFIG.k)
    log(f"serve: first-call wall (compile included) {fast[0]} {t_p:.2f}s, "
        f"ref {t_r:.2f}s, cpu ref {t_c:.2f}s; recall@10 {fast[0]} {rec_p} "
        f"vs cpu ref {rec_c}; chip-ref rows equal to cpu-ref rows: "
        f"{int((ids_r == ids_c).all(1).sum())}/{n_queries}")
    check(np.array_equal(ids_p, ids_r),
          f"serve: {fast[0]} ids == ref ids ({n_queries} queries x "
          f"{CONFIG.k})")
    check(np.array_equal(d_p, d_r), "serve: pallas dists == ref dists")
    check(rec_p >= rec_c, f"serve: recall@10 {rec_p} >= cpu {rec_c}")
    # The vector tier's load path: XOR-delta chunks decoded by byteplane.
    vs = DecoupledVectorStore(StoreConfig(
        dim=CONFIG.dim, dtype=np.uint8, vector_codec="xor_delta_huffman",
        kernels=pal.p.kernels))
    vs.append(np.arange(n), base)
    vs.seal_active()
    check(np.array_equal(vs.get(np.arange(n)), base),
          f"serve: vector-store load through byteplane={pal.p.kernels.byteplane}"
          f" returns the {n} stored vectors exactly")
    return dict(base=base, held_out=rows[n:], queries=queries, graph=graph,
                cb=cb, gt=gt)


def update_phase(seed: int, fast, world: dict) -> None:
    """One streaming round on the serve index, served from the handle."""
    from repro.configs.decouplevs_ann import CONFIG
    from repro.core.graph.pq import encode_pq
    from repro.core.storage.vector_store import (DecoupledVectorStore,
                                                 StoreConfig)
    from repro.core.update.fresh import StreamingIndex, UpdateConfig
    from repro.serve.ann import BatchedSearcher

    base, graph, cb = world["base"], world["graph"], world["cb"]
    n = len(base)
    vecs = base.astype(np.float32)
    vs = DecoupledVectorStore(StoreConfig(dim=vecs.shape[1],
                                          dtype=np.float32))
    vs.append(np.arange(n), vecs)
    vs.seal_active()
    t0 = time.perf_counter()
    idx = StreamingIndex(graph.adjacency, graph.medoid, vs,
                         encode_pq(vecs, cb), cb,
                         UpdateConfig(r=graph.r, l_build=L_BUILD,
                                      merge_threshold=10**9, kernels=fast))
    # Fresh points from the corpus's own distribution, under the next
    # dense ids.
    new = world["held_out"].astype(np.float32)
    n_update = len(new)
    new_ids = np.arange(n, n + n_update)
    # Delete ids the queries would otherwise return: the check bites.
    gone = np.unique(world["gt"])[:n_update]
    idx.insert(new_ids, new)
    idx.delete(gone)
    stats = idx.merge()
    searcher = BatchedSearcher(idx.handle, _params(CONFIG, graph.r, n,
                                                   kernels=fast))
    ids, _, rep = searcher.search(world["queries"])
    own, _, _ = searcher.search(new)
    log(f"update: insert {n_update} + delete {len(gone)} + merge "
        f"(full_rebuild={stats.full_rebuild}, {stats.dirty_vertices} dirty "
        f"vertices) + publish v{rep.snapshot_version} in "
        f"{time.perf_counter() - t0:.1f}s; kernels "
        f"{tuple(searcher.p.kernels)}")
    check(rep.snapshot_version >= 1, "update: batch pinned the published "
          f"snapshot v{rep.snapshot_version}")
    check(not np.isin(ids, gone).any(),
          f"update: none of the {len(gone)} deleted ids is returned")
    check(np.array_equal(own[:, 0], new_ids),
          f"update: each of the {n_update} inserted vectors is its own "
          f"nearest neighbour")


def capacity_phase(seed: int, fast, n: int = N_CAPACITY,
                   n_queries: int = 32) -> None:
    """The config's own widths at n rows of device state."""
    import jax
    from repro.configs.decouplevs_ann import CONFIG
    from repro.core.codec.elias_fano import slot_layout
    from repro.core.graph.pq import encode_pq, train_pq
    from repro.core.graph.vamana import VamanaGraph, medoid_of, random_start
    from repro.core.index import device_index_from_artifacts
    from repro.data.synthetic import make_queries, make_vector_dataset
    from repro.serve.ann import BatchedSearcher

    r = CONFIG.r
    t0 = time.perf_counter()
    base = make_vector_dataset("sift-like", n, CONFIG.dim, seed=seed)
    queries = make_queries("sift-like", n_queries, CONFIG.dim,
                           seed=seed).astype(np.float32)
    vecs = base.astype(np.float32)
    graph = VamanaGraph(random_start(n, r, np.random.default_rng(seed)),
                        medoid_of(vecs), r)
    cb = train_pq(vecs, m=CONFIG.pq_m, seed=seed)
    index = device_index_from_artifacts(vecs, graph, cb, encode_pq(vecs, cb))
    words = slot_layout(r, n)[3]
    state = sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(index))
    log(f"capacity: n={n} d={CONFIG.dim} R={r} pq_m={CONFIG.pq_m}, device "
        f"state {state / 1e9:.3f} GB (EF slots {words} words/row); set-up "
        f"{time.perf_counter() - t0:.1f}s. Cuts: the graph is build_vamana's"
        f" random degree-R start (a real build at this n is out of reach); "
        f"PQ trained on its default 20k sample")
    # The production lowering's 2^15-slot hashed visited set; the exact
    # set (visited_hash_bits=0) is checked at these widths by the
    # benchmark's bigann cell.
    p = _params(CONFIG, r, n, visited_hash_bits=15)
    ids_p, d_p, t_p = _serve(BatchedSearcher(index, p._replace(kernels=fast)),
                             queries)
    ids_r, _, t_r = _serve(BatchedSearcher(index, p._replace(
        kernels=_ref())), queries)
    stats = jax.devices()[0].memory_stats() or {}
    log(f"capacity: visited set hashed, 2^{p.visited_hash_bits} slots per "
        f"query; first-call wall {fast[0]} {t_p:.2f}s, ref {t_r:.2f}s; "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'not reported')}"
        f" of bytes_limit {stats.get('bytes_limit', 'not reported')}")
    check(np.array_equal(ids_p, ids_r),
          f"capacity: {fast[0]} ids == ref ids ({n_queries} queries)")
    exact = _exact_l2(base, queries, ids_p)
    check(np.allclose(d_p, exact, rtol=1e-6, atol=0),
          "capacity: every returned distance == float64 L2 of its id "
          f"(max abs diff {float(np.nanmax(np.abs(d_p - exact))):.3g})")


def sharded_phase(seed: int, fast, n: int = N_SERVE, r: int = R_SERVE,
                  n_queries: int = N_QUERIES, shards: int = SHARDS) -> None:
    """shard_map search over a mesh vs the serial per-shard path."""
    import jax
    from repro.configs.decouplevs_ann import CONFIG
    from repro.core.distributed.sharded_index import (build_sharded_index,
                                                      make_sharded_search,
                                                      place_on_mesh)
    from repro.core.index import recall_at_k
    from repro.data.synthetic import (ground_truth, make_queries,
                                      make_vector_dataset)
    from repro.serve.ann import BatchedSearcher

    base = make_vector_dataset("sift-like", n, CONFIG.dim, seed=seed)
    queries = make_queries("sift-like", n_queries, CONFIG.dim,
                           seed=seed).astype(np.float32)
    t0 = time.perf_counter()
    index, per = build_sharded_index(base, shards, r=r, l_build=L_BUILD,
                                     pq_m=CONFIG.pq_m, seed=seed)
    log(f"sharded: built {shards} shards x {per} rows in "
        f"{time.perf_counter() - t0:.1f}s")
    mesh = jax.make_mesh((shards,), ("data",),
                         devices=jax.devices()[:shards])
    placed = place_on_mesh(index, mesh)
    homes = set()
    for name, x in zip(index._fields, placed):
        devs = {s.device for s in x.addressable_shards}
        rows = sorted((s.index[0].start or 0) for s in x.addressable_shards)
        check(len(devs) == shards and rows == list(range(shards)),
              f"sharded: {name} has one shard on each of {len(devs)} "
              f"devices")
        homes.add(tuple(str(s.device) for s in sorted(
            x.addressable_shards, key=lambda s: s.index[0].start or 0)))
    check(len(homes) == 1, "sharded: shard i of every field is on the same "
          f"device: {sorted(homes)[0]}")
    p = _params(CONFIG, r, per, kernels=fast)
    run = make_sharded_search(mesh, p, merge="hier")
    serial = BatchedSearcher(index, p)
    bucket = max(serial.cfg.buckets)
    t0 = time.perf_counter()
    ids_m = np.concatenate([np.asarray(run(placed, queries[i:i + bucket])[0])
                            for i in range(0, n_queries, bucket)])
    t_m = time.perf_counter() - t0
    ids_s, _, t_s = _serve(serial, queries)
    gt = ground_truth(base, queries, k=CONFIG.k)
    log(f"sharded: kernels {tuple(serial.p.kernels)}; first-call wall mesh "
        f"{t_m:.2f}s, serial {t_s:.2f}s; recall@10 mesh "
        f"{recall_at_k(ids_m, gt, CONFIG.k)}")
    check(np.array_equal(ids_m, ids_s),
          f"sharded: make_sharded_search (hier) ids == serial BatchedSearcher"
          f" ids ({n_queries} queries)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, SHARDS), default=1,
                    help=f"{SHARDS}: run only the sharded phase, one shard "
                         "per chip")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform!r}; refusing "
              "to run on a fallback backend", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    from repro.kernels.dispatch import KernelConfig
    log(f"chip_smoke: {dev.device_kind} x{len(devices)}, jax "
        f"{jax.__version__}, seed {args.seed}, compile cache "
        f"{enable_compile_cache()}")
    fast = KernelConfig().resolve()
    want = KernelConfig(*(["pallas"] * len(KernelConfig._fields)))
    phases = []
    try:
        check(fast == want, f"auto resolves every op to compiled pallas: "
              f"{dict(zip(fast._fields, fast))}")
    except CheckFailed:
        phases.append("resolve")
    world = {}

    def run(name, fn, *a):
        t0 = time.perf_counter()
        log(f"== {name}")
        try:
            world[name] = fn(args.seed, fast, *a)
            log(f"== {name} passed in {time.perf_counter() - t0:.1f}s")
        except Exception:
            traceback.print_exc(file=sys.stdout)
            phases.append(name)
            log(f"== {name} FAILED after {time.perf_counter() - t0:.1f}s")

    if args.chips == SHARDS:
        run("sharded", sharded_phase)
    else:
        run("serve", serve_phase)
        if "serve" in world:
            run("update", update_phase, world["serve"])
        else:
            phases.append("update (no serve index)")
        run("capacity", capacity_phase)
    if phases:
        log(f"chip_smoke: FAILED: {', '.join(phases)}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
