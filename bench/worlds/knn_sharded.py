"""A ``ShardedIndex`` over ``shards`` contiguous ranges of the base, laid
out one shard per chip: each shard has its own degree-R kNN graph (built on
the device by ``bench/worlds/knn.py``'s ``knn_graph``), its own PQ codebook
and codes, and Elias-Fano slots at the whole base's id universe (the
harness sets ``SearchParams.universe`` to the configuration's ``rows``).

The host's share of each shard (PQ training and encoding, the EF slots)
runs in worker processes, every shard at once, while the device builds the
next shard's graph: one after another the shards would take some four times
the host set-up of a single index of the same rows. The workers run the
program's own NumPy functions and touch no device.
"""
import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor

import numpy as np


def build(config: dict, base: np.ndarray, seed: int):
    """-> the ``ShardedIndex`` of ``base`` under ``config``, placed one
    shard per device over a one-axis mesh of the first ``shards``
    devices."""
    import jax
    from bench import spec
    from repro.core.distributed.sharded_index import (ShardedIndex,
                                                      place_on_mesh)
    from repro.core.graph.pq import encode_pq, train_pq
    from repro.core.graph.vamana import VamanaGraph, medoid_of
    from repro.core.index import ef_slots_from_graph

    if config["partition"] != "range":
        raise ValueError(f"partition {config['partition']!r}: only 'range'")
    knn_graph = spec.load_module(spec.BENCH_DIR / "worlds" / "knn.py"
                                 ).knn_graph
    s, rows, r = config["shards"], config["rows"], config["graph_degree"]
    if rows % s:
        raise ValueError(f"{rows} rows do not split into {s} equal shards")
    parts = np.split(base, s)
    workers = min(2 * s, os.cpu_count() or 1)
    with ProcessPoolExecutor(workers, multiprocessing.get_context("spawn")
                             ) as pool:
        books = [pool.submit(train_pq, part, m=config["pq_m"],
                             k=config["pq_centroids"], seed=seed + i)
                 for i, part in enumerate(parts)]
        graphs, slots = [], []
        for part in parts:
            g = VamanaGraph(list(knn_graph(part, r)),
                            medoid_of(part.astype(np.float32)), r)
            graphs.append(g)
            slots.append(pool.submit(ef_slots_from_graph, g, rows))
        codes = [pool.submit(encode_pq, part, book.result())
                 for part, book in zip(parts, books)]
        books = [b.result() for b in books]
        slots = [f.result() for f in slots]
        codes = [f.result() for f in codes]
    nbrs, counts = zip(*(g.to_padded() for g in graphs))
    index = ShardedIndex(
        neighbors=np.stack(nbrs), counts=np.stack(counts),
        ef_slots=np.stack(slots), pq_codes=np.stack(codes),
        pq_centroids=np.stack([b.centroids for b in books]),
        vectors=np.stack(parts).astype(np.float32),
        medoid=np.array([g.medoid for g in graphs], np.int32),
        row_ids=np.arange(rows, dtype=np.int32).reshape(s, -1))
    mesh = jax.make_mesh((s,), ("data",), devices=jax.devices()[:s])
    return place_on_mesh(index, mesh)
