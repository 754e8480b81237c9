"""Work one beam hop's compute tail needs (ADC of the newly seen neighbors
plus the top-L merge), summed over the window, from the program's counts.

Counted per hop of one query, whatever the kernel does besides:

- bytes: the LUT (M x centroids x 4), the candidate list read and written
  (L ids and L distances, 4 bytes each, both ways), and for each new
  neighbor its M code bytes and its 4-byte id;
- ops: M adds per new neighbor's distance, and a merge of the new
  neighbors into the list of L: one compare per list slot and per new
  neighbor.

New neighbors are the ``pq_ops`` count (ADC distances computed); hops are
lists fetched over the beam width. The adds are float32 adds, for which
no peak is published; the bf16 matrix peak stands in, and the kernel is
bound by its bytes by orders of magnitude either way.
"""
from bench.readers import lists_fetched

PEAK = "bf16_flops_per_s"


def work(run):
    cfg = run.cell.config
    lists, new = lists_fetched(run), run.counters.get("pq_ops")
    if not lists or new is None:
        return None
    m, L = cfg["pq_m"], cfg["search_list"]
    hops = lists / cfg["beam_width"]
    ops = new * m + new + hops * L
    nbytes = hops * (m * cfg["pq_centroids"] * 4 + 4 * L * 4) + new * (m + 4)
    return ops, nbytes
