"""Work decoding the fetched adjacency lists needs, summed over the window.

Counted per fetched list, whatever the kernel does besides:

- bytes: its Elias-Fano slot read whole (the fixed-size slot the index
  stores, ``ef_slots`` words of 4 bytes) and its decoded ids written
  (the graph's mean degree, 4 bytes each, and a 4-byte count);
- ops: one per decoded id.

The ops are integer ops, for which no peak is published; the int8 matrix
peak stands in, and the kernel is bound by its bytes by orders of
magnitude either way.
"""
from bench.readers import lists_fetched

PEAK = "int8_ops_per_s"


def work(run):
    lists = lists_fetched(run)
    if not lists or "ef_slots" not in run.state_shapes:
        return None
    words = run.state_shapes["ef_slots"][1]
    ops = lists * run.mean_degree
    nbytes = lists * (words * 4 + run.mean_degree * 4 + 4)
    return ops, nbytes
