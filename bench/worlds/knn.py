"""An approximate k-nearest-neighbour graph of degree R, built on the device
from the base, standing in for a Vamana graph at sizes the pure-Python
``build_vamana`` cannot reach, with PQ codes and Elias-Fano adjacency slots
as the program lays them out.

Each row's R neighbours are its nearest rows by squared L2, found with
``jax.lax.approx_min_k`` (recall target 0.95), which the TPU compiler fuses
into the distance matmul. The data are integers below 256, exact in
bfloat16, and every dot product is an integer below 2^24, exact in the
float32 accumulator: the graph is a true kNN graph up to the approximate
top-k. Unlike a random graph, greedy search over it converges on the
query's neighbourhood, so recall measures the traversal.
"""
import numpy as np


def knn_graph(base: np.ndarray, r: int, block: int = 1024) -> np.ndarray:
    """-> [n, r] int32: each row's r approximate nearest other rows."""
    import jax
    import jax.numpy as jnp

    n = len(base)
    block = min(block, n)
    x = jnp.asarray(base, jnp.bfloat16)
    half = 0.5 * jnp.sum(jnp.square(x.astype(jnp.float32)), axis=1)

    @jax.jit
    def step(x, half, start):
        q = jax.lax.dynamic_slice_in_dim(x, start, block)
        # 0.5 |x|^2 - <q, x> orders rows by |q - x|^2 for a fixed q.
        d = half[None, :] - jnp.dot(q, x.T,
                                    preferred_element_type=jnp.float32)
        return jax.lax.approx_min_k(d, r + 1, recall_target=0.95)[1]

    out = np.empty((n, r + 1), np.int32)
    for s in range(0, n, block):
        s = min(s, n - block)               # one shape for every block
        out[s:s + block] = np.asarray(step(x, half, s))
    # Drop each row itself, or its farthest candidate where the
    # approximate top-k missed it.
    own = out == np.arange(n, dtype=np.int32)[:, None]
    drop = np.where(own.any(1), own.argmax(1), r)
    keep = np.ones_like(out, bool)
    keep[np.arange(n), drop] = False
    return out[keep].reshape(n, r)


def build(config: dict, base: np.ndarray, seed: int):
    """-> the ``DeviceIndex`` of ``base`` under ``config``."""
    from repro.core.graph.pq import encode_pq, train_pq
    from repro.core.graph.vamana import VamanaGraph, medoid_of
    from repro.core.index import device_index_from_artifacts

    vecs = base.astype(np.float32)
    r = config["graph_degree"]
    graph = VamanaGraph(list(knn_graph(base, r)), medoid_of(vecs), r)
    cb = train_pq(vecs, m=config["pq_m"], k=config["pq_centroids"],
                  seed=seed)
    return device_index_from_artifacts(vecs, graph, cb, encode_pq(vecs, cb))
