"""A real Vamana graph, built by the program's ``build_vamana``, with PQ
codes and Elias-Fano adjacency slots as the program lays them out."""
import numpy as np


def build(config: dict, base: np.ndarray, seed: int):
    """-> the ``DeviceIndex`` of ``base`` under ``config``."""
    from repro.core.graph.pq import encode_pq, train_pq
    from repro.core.graph.vamana import build_vamana
    from repro.core.index import device_index_from_artifacts

    vecs = base.astype(np.float32)
    graph = build_vamana(vecs, r=config["graph_degree"],
                         l_build=config["build_list"], alpha=config["alpha"],
                         seed=seed)
    cb = train_pq(vecs, m=config["pq_m"], k=config["pq_centroids"],
                  seed=seed)
    return device_index_from_artifacts(vecs, graph, cb, encode_pq(vecs, cb))
