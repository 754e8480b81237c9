"""``ef_slots_from_graph`` encodes every adjacency list of a graph at once
(``elias_fano.encode_slots``): each row equals ``encode_slot`` of that
list, sorted, at the graph's degree bound and the given id universe."""
import numpy as np
import pytest

from repro.core.codec import elias_fano as ef
from repro.core.graph.vamana import VamanaGraph
from repro.core.index import ef_slots_from_graph


# (r, universe, lists): the bigann cells' slot layouts (2^20 and 2^18),
# sift's, no low bits (universe <= r), a universe below r, 16 low bits, and
# more lists than one block of rows.
@pytest.mark.parametrize("r,universe,n", [(128, 1 << 20, 257),
                                          (128, 1 << 18, 257),
                                          (32, 8192, 257), (16, 16, 257),
                                          (8, 5, 257), (24, 10**6, 257),
                                          (16, 1 << 12, 2600)])
def test_batched_slots_equal_per_list_slots(r, universe, n):
    rng = np.random.default_rng(r * 7 + universe)
    lists = []
    for i in range(n):
        # Full, empty and partial lists, unsorted, the largest id included.
        k = min(universe, r if i % 3 == 0 else int(rng.integers(0, r + 1)))
        a = rng.choice(universe, size=k, replace=False)
        if i == 5 and k:
            a[0] = universe - 1
        lists.append(a.astype(np.int32))
    graph = VamanaGraph(lists, 0, r)
    want = np.stack([ef.encode_slot(np.sort(a.astype(np.uint64)), r, universe)
                     for a in lists])
    got = ef_slots_from_graph(graph, universe=universe)
    assert got.dtype == np.uint32
    np.testing.assert_array_equal(got, want)


def test_default_universe_is_the_graph_size():
    rng = np.random.default_rng(0)
    lists = [rng.choice(300, size=12, replace=False).astype(np.int32)
             for _ in range(300)]
    graph = VamanaGraph(lists, 0, 12)
    np.testing.assert_array_equal(ef_slots_from_graph(graph),
                                  ef_slots_from_graph(graph, universe=300))


def test_a_list_longer_than_r_is_refused():
    graph = VamanaGraph([np.arange(5, dtype=np.int32)], 0, 4)
    with pytest.raises(ValueError):
        ef_slots_from_graph(graph, universe=16)
