"""Mesh-scale sharded ANNS over simulated 8–32 device topologies (§5).

The dataset is partitioned — contiguous id ranges or balanced k-means
clusters — into one Vamana sub-graph + PQ codes + compressed stores per
shard, sharded over the ``data`` (x ``pod``) mesh axes. A query batch is
replicated; `shard_map` runs the hand-batched device beam search
(`search_batched`, one while_loop for the whole batch) per shard, then the
per-shard top-K candidates meet in one of two merges:

- **flat**: one `all_gather` of K rows per shard + a global top-K over the
  K·S gathered candidates (the original smoke-level path — gathered bytes
  grow linearly in S);
- **hierarchical** (default): a butterfly/tree merge per mesh axis,
  innermost (intra-node) axis first — each of the log2(S_axis) steps
  exchanges only K already-reduced rows with the XOR partner
  (`jax.lax.ppermute`), so a device receives K·Σ log2(S_axis) rows
  instead of K·S (`merge_comm_rows` is the model both the bench and the
  engine pricing use). Non-power-of-two axes fall back to the flat gather
  for that axis only.

**Selective shard routing** (SPANN's closest-posting-list pruning): a
replicated :class:`ShardRouter` — per-shard k-means centroids over the
shard's own rows — scores shards per query; only the top
``ceil(route_frac * S)`` shards keep their candidates, the rest contribute
(-1, +inf) rows at zero modeled I/O. Routing only preserves recall when the
partition is *clustered* (``partition="cluster"``); with contiguous id
ranges every shard sees the whole space and pruning is lossy.

Local ids translate to global ids through ``ShardedIndex.row_ids`` (the
per-slot global id map; -1 marks the pad rows that fill the last shard to a
uniform size) — pad rows are therefore masked out of every merge instead of
surfacing duplicate ids at tiny K.

Scale notes (1000+ nodes): shards are independent -> elastic re-sharding is
re-partitioning; a failed shard degrades recall gracefully until its
replica is promoted (search merges whatever shards respond — the serving
tier's ``failed_shards`` arm); the `model` axis stays free for the serving
LM (RAG collocation) or for TP-split re-ranking.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..index import build_device_index
from ..search.beam import (DeviceIndex, SearchParams, resolve_kernels,
                           search_batched)


class ShardedIndex(NamedTuple):
    """Per-shard DeviceIndex arrays stacked on a leading shard axis."""
    neighbors: jnp.ndarray      # [S, n, R]
    counts: jnp.ndarray         # [S, n]
    ef_slots: jnp.ndarray       # [S, n, W]
    pq_codes: jnp.ndarray       # [S, n, M]
    pq_centroids: jnp.ndarray   # [S, M, K, dsub]
    vectors: jnp.ndarray        # [S, n, d]
    medoid: jnp.ndarray         # [S]
    row_ids: jnp.ndarray        # [S, n] int32 global id per local slot;
                                # -1 = pad row (masked out of every merge)


class ShardRouter(NamedTuple):
    """Replicated per-shard centroids: score[q, s] = min_c ||q - c_{s,c}||²
    (SPANN closest-posting-list routing, one hot router per query batch)."""
    centroids: jnp.ndarray      # [S, C, d] float32


N_SHARD_FIELDS = len(ShardedIndex._fields)


# ------------------------------------------------------------- partitioning
def _kmeans(x: np.ndarray, k: int, rng, iters: int = 8) -> np.ndarray:
    """Plain seeded Lloyd's over [n, d] -> [k, d] centroids (empty clusters
    re-seeded from the farthest points so k centroids always come back)."""
    n = len(x)
    cent = x[rng.choice(n, size=min(k, n), replace=False)].astype(np.float64)
    if len(cent) < k:
        cent = np.concatenate([cent, np.repeat(cent[-1:], k - len(cent), 0)])
    for _ in range(iters):
        d2 = ((x[:, None, :] - cent[None]) ** 2).sum(-1)      # [n, k]
        asn = d2.argmin(1)
        for c in range(k):
            m = asn == c
            if m.any():
                cent[c] = x[m].mean(0)
            else:
                cent[c] = x[d2.min(1).argmax()]
    return cent.astype(np.float32)


def _partition(vectors: np.ndarray, n_shards: int, per: int, mode: str,
               seed: int) -> list:
    """-> list of [<= per] int64 global-id arrays, one per shard."""
    n = len(vectors)
    if mode == "range":
        return [np.arange(i * per, min((i + 1) * per, n), dtype=np.int64)
                for i in range(n_shards)]
    if mode != "cluster":
        raise ValueError(f"partition must be 'range' or 'cluster', "
                         f"got {mode!r}")
    rng = np.random.default_rng(seed)
    # Two-level SPANN-style partition: fine k-means clusters (several per
    # shard) are laid out along a greedy nearest-centroid TOUR and chopped
    # into ``per``-sized contiguous shards. Nearby clusters — sub-clusters
    # of one data mode included — are adjacent on the tour, so a mode lands
    # on one shard except at the <= S-1 chop boundaries (each split spans
    # exactly two ADJACENT shards). A query's neighbors live in one mode;
    # keeping modes co-sharded is what makes selective routing
    # recall-preserving, where a point-level balanced assignment would
    # scatter boundary modes and cap routed recall well below full fan-out.
    n_fine = min(n, max(n_shards, min(8 * n_shards, n // 8 or 1)))
    cent = _kmeans(vectors.astype(np.float64), n_fine, rng)
    d2 = ((vectors[:, None, :] - cent[None].astype(np.float64)) ** 2).sum(-1)
    asn = d2.argmin(1)
    clusters = [np.nonzero(asn == c)[0] for c in range(n_fine)]
    live = [c for c in range(n_fine) if len(clusters[c])]
    means = np.stack([vectors[clusters[c]].mean(0) for c in live]) \
        .astype(np.float64)
    cd2 = ((means[:, None, :] - means[None]) ** 2).sum(-1)
    tour, left = [0], set(range(1, len(live)))
    while left:
        prev = tour[-1]
        nxt = min(left, key=lambda c: (cd2[prev, c], c))
        tour.append(nxt)
        left.remove(nxt)
    order = np.concatenate([clusters[live[c]] for c in tour])
    return [np.asarray(b, np.int64) for b in np.array_split(order, n_shards)]


def build_sharded_index(vectors: np.ndarray, n_shards: int, r: int = 32,
                        l_build: int = 64, pq_m: int = 8, seed: int = 0,
                        partition: str = "range"
                        ) -> tuple[ShardedIndex, int]:
    """-> (stacked per-shard index, shard rows ``per``).

    Shards with fewer than ``per`` members are padded with duplicates of
    their last row so the stack is rectangular; pad slots carry
    ``row_ids == -1`` and are masked out of every merge (they can never
    surface as duplicate ids in a merged top-K).
    """
    vectors = np.asarray(vectors, np.float32)
    n = len(vectors)
    per = -(-n // n_shards)
    parts, row_ids = [], []
    for i, gids in enumerate(_partition(vectors, n_shards, per, partition,
                                        seed)):
        assert len(gids) > 0, f"shard {i} is empty (n={n}, S={n_shards})"
        sub = vectors[gids]
        pad = per - len(gids)
        if pad:      # duplicate the last member; masked via row_ids == -1
            sub = np.concatenate([sub, np.repeat(sub[-1:], pad, 0)])
        idx, _, _ = build_device_index(sub, r=r, l_build=l_build, pq_m=pq_m,
                                       seed=seed + i)
        parts.append(idx)
        row_ids.append(np.concatenate(
            [gids, np.full(pad, -1, np.int64)]).astype(np.int32))
    stack = lambda field: jnp.stack([getattr(p, field) for p in parts])
    return ShardedIndex(
        neighbors=stack("neighbors"), counts=stack("counts"),
        ef_slots=stack("ef_slots"), pq_codes=stack("pq_codes"),
        pq_centroids=stack("pq_centroids"), vectors=stack("vectors"),
        medoid=jnp.stack([p.medoid for p in parts]),
        row_ids=jnp.asarray(np.stack(row_ids))), per


# ------------------------------------------------------------------ routing
def build_router(index: ShardedIndex, c: int = 4, seed: int = 0
                 ) -> ShardRouter:
    """k-means ``c`` centroids per shard over its REAL rows (pad rows
    excluded via row_ids) — the replicated routing table."""
    vecs = np.asarray(index.vectors, np.float32)
    rids = np.asarray(index.row_ids)
    cents = []
    for s in range(vecs.shape[0]):
        rows = vecs[s][rids[s] >= 0]
        cents.append(_kmeans(rows.astype(np.float64), c,
                             np.random.default_rng(seed + s)))
    return ShardRouter(centroids=jnp.asarray(np.stack(cents)))


def route_mask(centroids, queries, route_frac: float):
    """[S, C, d] centroids x [Q, d] queries -> bool [Q, S]: the top
    ``ceil(route_frac * S)`` shards per query by min-centroid distance.
    jnp throughout — usable inside jit (mesh path) and from numpy callers
    (host path takes ``np.asarray`` of the result)."""
    centroids = jnp.asarray(centroids, jnp.float32)
    queries = jnp.asarray(queries, jnp.float32)
    s = centroids.shape[0]
    m = max(1, min(s, int(-(-route_frac * s // 1))))
    d2 = ((queries[:, None, None, :] - centroids[None]) ** 2).sum(-1)
    score = d2.min(-1)                                        # [Q, S]
    _, idx = jax.lax.top_k(-score, m)                         # [Q, m]
    q = queries.shape[0]
    return jnp.zeros((q, s), jnp.bool_).at[
        jnp.arange(q)[:, None], idx].set(True)


# ------------------------------------------------------------------- merges
def _axis_names_sizes(mesh, axis) -> tuple[tuple, tuple]:
    names = (axis,) if isinstance(axis, str) else tuple(axis)
    return names, tuple(int(mesh.shape[a]) for a in names)


def merge_comm_rows(k: int, axis_sizes, mode: str = "hier") -> int:
    """(id, dist) rows RECEIVED per device during the merge — the comm
    model the bench's gathered-bytes acceptance and the engine's
    ``shard_merge_cost_us`` both price. flat: K·S. hier: K·Σ log2(axis)
    (butterfly), non-power-of-two axes priced flat for that axis."""
    sizes = [int(s) for s in (axis_sizes if np.ndim(axis_sizes) else
                              [axis_sizes])]
    if mode == "flat":
        return k * int(np.prod(sizes))
    rows = 0
    for s in sizes:
        if s <= 1:
            continue
        rows += k * s if s & (s - 1) else k * int(round(np.log2(s)))
    return rows


def _lex_topk(ids, d, k):
    """[Q, M] candidates -> top-k by (distance, id) lexicographic order —
    the deterministic tie-break every merge stage shares, so the final
    top-K is independent of merge topology (flat vs tree, any axis order).
    Pad rows (id -1, dist +inf) sink to the tail."""
    big = jnp.iinfo(jnp.int32).max
    order = jnp.argsort(jnp.where(ids < 0, big, ids), axis=1)
    ids = jnp.take_along_axis(ids, order, 1)
    d = jnp.take_along_axis(d, order, 1)
    order = jnp.argsort(d, axis=1, stable=True)
    return (jnp.take_along_axis(ids, order, 1)[:, :k],
            jnp.take_along_axis(d, order, 1)[:, :k])


def _merge_axis_flat(ids, d, name, k):
    all_i = jax.lax.all_gather(ids, name)                     # [s, Q, K]
    all_d = jax.lax.all_gather(d, name)
    s, q = all_i.shape[0], all_i.shape[1]
    return _lex_topk(all_i.transpose(1, 0, 2).reshape(q, -1),
                     all_d.transpose(1, 0, 2).reshape(q, -1), k)


def _merge_axis_tree(ids, d, name, size, k):
    """Butterfly (recursive-doubling) top-K on one mesh axis: log2(size)
    ppermute steps with the XOR partner, each exchanging only the K
    already-reduced rows; afterwards every device on the axis holds the
    identical axis-global top-K."""
    step = 1
    while step < size:
        perm = [(i, i ^ step) for i in range(size)]
        o_ids = jax.lax.ppermute(ids, name, perm)
        o_d = jax.lax.ppermute(d, name, perm)
        ids, d = _lex_topk(jnp.concatenate([ids, o_ids], 1),
                           jnp.concatenate([d, o_d], 1), k)
        step *= 2
    return ids, d


def _sharded_fn(mesh, p: SearchParams, axis, merge: str = "hier",
                routed: bool = False):
    """The shard_map program: local beam search -> global-id translation
    (+ routing mask) -> hierarchical or flat merge. Returns a function of
    (*ShardedIndex fields, queries[, route mask])."""
    if merge not in ("hier", "flat"):
        raise ValueError(f"merge must be 'hier' or 'flat', got {merge!r}")
    # Config time: kernel backends are pinned BEFORE shard_map builds the
    # program, so per-shard traces never consult the platform (the dispatch
    # layer's contract on mixed-backend meshes) — resolved against the
    # MESH's platform, not the driving process's default backend.
    p = resolve_kernels(p, platform=mesh.devices.flat[0].platform)
    names, sizes = _axis_names_sizes(mesh, axis)

    def local_search(nbrs, cnts, slots, codes, cents, vecs, medoid, rids,
                     queries, *mask):
        local = DeviceIndex(
            neighbors=nbrs[0], counts=cnts[0], ef_slots=slots[0],
            pq_codes=codes[0], pq_centroids=cents[0], vectors=vecs[0],
            medoid=medoid[0])
        ids, dists, _ = search_batched(local, queries, p)
        # Global ids through the shard's row_ids map; pad rows (-1) and
        # empty result slots both land at (-1, +inf), so they can never
        # outrank a real candidate in any merge stage.
        gids = jnp.where(ids >= 0,
                         rids[0][jnp.clip(ids, 0, rids.shape[1] - 1)], -1)
        d = jnp.where(gids >= 0, dists, jnp.inf)
        if routed:
            shard_idx = sum(
                jax.lax.axis_index(a) * int(np.prod(sizes[i + 1:], dtype=int))
                for i, a in enumerate(names))
            mine = mask[0][:, shard_idx]                      # [Q] bool
            gids = jnp.where(mine[:, None], gids, -1)
            d = jnp.where(mine[:, None], d, jnp.inf)
        # Innermost (intra-node) axis first: candidates are reduced to K
        # per node before any cross-node exchange.
        for name, size in reversed(list(zip(names, sizes))):
            if merge == "hier" and size & (size - 1) == 0:
                gids, d = _merge_axis_tree(gids, d, name, size, p.k)
            else:
                gids, d = _merge_axis_flat(gids, d, name, p.k)
        return gids, d

    n_in = N_SHARD_FIELDS
    extra = (P(),) if routed else ()
    return jax.shard_map(local_search, mesh=mesh,
                         in_specs=(P(axis),) * n_in + (P(),) + extra,
                         out_specs=(P(), P()), check_vma=False)


def make_sharded_search(mesh, p: SearchParams, axis="data",
                        merge: str = "hier", router: ShardRouter = None,
                        route_frac: float = 1.0):
    """-> jit'd search(index: ShardedIndex, queries [Q, d]) -> (ids, dists).

    ``merge="hier"`` runs the butterfly tree merge per mesh axis (innermost
    first); ``"flat"`` is the K·S all_gather baseline. With a ``router``,
    each query's candidates are masked to its top ``ceil(route_frac * S)``
    shards before the merge (``route_frac=1.0`` is bit-identical to no
    router — the full fan-out contract the test tier pins).
    """
    fn = _sharded_fn(mesh, p, axis, merge=merge, routed=router is not None)
    if router is None:
        @jax.jit
        def run(index: ShardedIndex, queries):
            return fn(*index, queries)
    else:
        cents = jnp.asarray(router.centroids)

        @jax.jit
        def run(index: ShardedIndex, queries):
            mask = route_mask(cents, queries, route_frac)
            return fn(*index, queries, mask)
    return run


def place_on_mesh(index: ShardedIndex, mesh, axis="data") -> ShardedIndex:
    spec = NamedSharding(mesh, P(axis))
    return ShardedIndex(*(jax.device_put(x, spec) for x in index))


def lower_production_search(mesh, ann_cfg, p: SearchParams | None = None,
                            merge: str = "hier"):
    """Abstract lowering of the paper's own workload on the production mesh
    (the `decouplevs-ann` dry-run cell): per-shard EF graph + PQ codes +
    rerank vectors, ShapeDtypeStruct only (no allocation).

    The dataset shards over EVERY mesh axis (traversal keeps the `model`
    axis idle, so using it for shards multiplies aggregate HBM): 1B vectors
    over 256/512 shards -> ~2 GiB of compressed index + rerank tier per
    chip. The raw-adjacency ablation tensor is a 1-entry stub (the
    compressed EF slots are the production representation). The default
    hierarchical merge keeps the cross-pod exchange at K·log2 rows per
    device (`merge_comm_rows`)."""
    from ..codec.elias_fano import slot_layout
    axis = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axis]))
    per = -(-ann_cfg.n_vectors // n_shards)
    p = p or SearchParams(l_size=ann_cfg.l_size, beam_width=ann_cfg.beam_width,
                          k=ann_cfg.k, rerank_batch=ann_cfg.rerank_batch,
                          r_max=ann_cfg.r, universe=per, max_iters=64,
                          use_ef=True,
                          # §Perf iteration B: O(2^15) hash visited-set
                          # instead of O(n_shard) bool arrays per query.
                          visited_hash_bits=15)
    _, _, _, slot_words = slot_layout(ann_cfg.r, per)
    f = jax.ShapeDtypeStruct
    dt = jnp.dtype(ann_cfg.dtype)
    args = (
        f((n_shards, 1, ann_cfg.r), jnp.int32),
        f((n_shards, per), jnp.int32),
        f((n_shards, per, slot_words), jnp.uint32),
        f((n_shards, per, ann_cfg.pq_m), jnp.uint8),
        f((n_shards, ann_cfg.pq_m, 256, ann_cfg.dim // ann_cfg.pq_m),
          jnp.float32),
        f((n_shards, per, ann_cfg.dim), dt),
        f((n_shards,), jnp.int32),
        f((n_shards, per), jnp.int32),                        # row_ids
        f((ann_cfg.query_batch, ann_cfg.dim), jnp.float32),
    )
    spec = NamedSharding(mesh, P(axis))
    rep = NamedSharding(mesh, P())
    fn = _sharded_fn(mesh, p, axis, merge=merge)
    jitted = jax.jit(fn, in_shardings=(spec,) * N_SHARD_FIELDS + (rep,),
                     out_shardings=(rep, rep))
    return jitted.lower(*args)
