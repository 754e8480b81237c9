"""End-to-end index construction: Vamana graph + PQ codes + compressed
device-resident structures (paper §3.1 architecture, JAX edition).

``build_device_index`` is the offline path: build the graph (expensive, as in
the paper), then apply DecoupleVS's compression/layout transform (cheap) to
produce the HBM-resident search state. The host-tier stores (segments, block
layouts, Huffman payloads) live in ``core.storage`` and are built from the
same artifacts.
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from .codec.elias_fano import encode_slots
from .graph.pq import PQCodebook, encode_pq, train_pq
from .graph.vamana import VamanaGraph, build_vamana
from .search.beam import DeviceIndex


def ef_slots_from_graph(graph: VamanaGraph, universe: int | None = None
                        ) -> np.ndarray:
    """Encode every adjacency list (sorted ascending — search is
    order-independent, §3.2) into fixed-size EF slots."""
    universe = universe or graph.n
    if any(len(a) > graph.r for a in graph.adjacency):
        raise ValueError(f"an adjacency list is longer than r={graph.r}")
    nbrs, counts = graph.to_padded()
    # Pads sort last as universe - 1, which encode_slots writes for them.
    nbrs = np.sort(np.where(nbrs < 0, universe - 1, nbrs), axis=1)
    return encode_slots(nbrs, counts, graph.r, universe)


def device_index_from_artifacts(vectors: np.ndarray, graph: VamanaGraph,
                                cb: PQCodebook, codes: np.ndarray
                                ) -> DeviceIndex:
    """Assemble the HBM-resident search state from pre-built offline
    artifacts (graph + PQ) — the cheap DecoupleVS transform, reusable when a
    graph already exists (benchmark worlds, serving warm-starts)."""
    nbrs, counts = graph.to_padded()
    slots = ef_slots_from_graph(graph)
    return DeviceIndex(
        neighbors=jnp.asarray(nbrs),
        counts=jnp.asarray(counts),
        ef_slots=jnp.asarray(slots),
        pq_codes=jnp.asarray(codes),
        pq_centroids=jnp.asarray(cb.centroids),
        vectors=jnp.asarray(vectors, dtype=jnp.float32),
        medoid=jnp.int32(graph.medoid),
    )


def build_device_index(vectors: np.ndarray, r: int = 32, l_build: int = 64,
                       alpha: float = 1.2, pq_m: int = 8, seed: int = 0
                       ) -> tuple[DeviceIndex, VamanaGraph, PQCodebook]:
    vectors = np.asarray(vectors, dtype=np.float32)
    graph = build_vamana(vectors, r=r, l_build=l_build, alpha=alpha, seed=seed)
    cb = train_pq(vectors, m=pq_m, seed=seed)
    codes = encode_pq(vectors, cb)
    return device_index_from_artifacts(vectors, graph, cb, codes), graph, cb


def verify_index_slots(index: DeviceIndex, r_max: int,
                       universe: int | None = None, kernels=None) -> bool:
    """Decode every EF slot through the kernel dispatch layer and check it
    reproduces the raw adjacency exactly (the compressed index tier is
    lossless — the paper's Q1 fidelity requirement, checked with whatever
    backend ``kernels`` names: jnp oracle or the Pallas decode kernel).

    Slots store adjacency sorted ascending (order-independent search,
    §3.2), so the raw lists are compared as sorted sets.
    """
    from repro.kernels import dispatch
    n, r = index.neighbors.shape
    universe = universe or n
    vals, cnts = dispatch.ef_decode(index.ef_slots, r_max, universe, kernels)
    if not bool(jnp.all(cnts == index.counts)):
        return False
    j = jnp.arange(max(r, r_max), dtype=jnp.int32)
    dec = jnp.where(j[None, :r_max] < cnts[:, None], vals, universe)
    raw = jnp.where(j[None, :r] < index.counts[:, None],
                    index.neighbors, universe)
    width = max(r, r_max)
    pad = lambda a: jnp.pad(a, ((0, 0), (0, width - a.shape[1])),
                            constant_values=universe)
    return bool(jnp.all(jnp.sort(pad(dec), 1) == jnp.sort(pad(raw), 1)))


def recall_at_k(pred_ids: np.ndarray, gt_ids: np.ndarray, k: int) -> float:
    """Fraction of true top-k found (paper's recall@10 metric, §4.1)."""
    hits = 0
    for p, g in zip(np.asarray(pred_ids), np.asarray(gt_ids)):
        hits += len(set(p[:k].tolist()) & set(g[:k].tolist()))
    return hits / (len(gt_ids) * k)
