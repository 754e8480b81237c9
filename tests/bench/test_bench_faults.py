"""With the timed path broken underneath, a run on the CPU comes out not
correct, once for each fault a cell can have on one chip."""
import time

import numpy as np
import pytest

from bench_testing import tiny_cell  # noqa: F401
from bench import harness


def stale():
    """Every call returns the previous call's answers: state unchanged."""
    prev = {}

    def fault(ids, dists, q):
        out = prev.get("out", (ids, dists))
        prev["out"] = (ids, dists)
        return (np.resize(out[0], ids.shape), np.resize(out[1], dists.shape))
    return fault


def half_left_out():
    """Only the first half of the batch is searched; the rest get its
    answers."""
    def fault(ids, dists, q):
        h = len(ids) // 2
        if h:
            ids, dists = ids.copy(), dists.copy()
            ids[h:2 * h], dists[h:2 * h] = ids[:h], dists[:h]
        return ids, dists
    return fault


def altered():
    """One id of each call changed where it is produced."""
    def fault(ids, dists, q):
        ids = ids.copy()
        ids[0, 0] = (ids[0, 0] + 1) % 1024
        return ids, dists
    return fault


def traversal_cut():
    """The traversal stops after its first hop: every answer is well formed
    and exactly re-ranked, but found near the entry point."""
    return "max_iters", 1


CASES = [("bigann-share-1m.closed32", stale),
         ("bigann-share-1m.closed32", half_left_out),
         ("bigann-share-1m.closed32", altered),
         ("bigann-share-1m.closed32", traversal_cut),
         ("sift-vamana-2k.open", traversal_cut),
         ("sift-vamana-2k.open", stale),
         ("sift-vamana-2k.open", altered)]


@pytest.mark.parametrize("name,fault", CASES,
                         ids=[f"{c}-{f.__name__}" for c, f in CASES])
def test_fault_is_not_correct(tiny_cell, monkeypatch, name, fault):
    from repro.serve.ann import BatchedSearcher
    broken = fault()
    if isinstance(broken, tuple):       # a search parameter set wrong
        init = BatchedSearcher.__init__

        def faulty_init(self, index, p, *a, **kw):
            init(self, index, p._replace(**dict([broken])), *a, **kw)
        monkeypatch.setattr(BatchedSearcher, "__init__", faulty_init)
    else:                               # answers altered as returned
        search = BatchedSearcher.search

        def faulty(self, queries, *a, **kw):
            ids, dists, rep = search(self, queries, *a, **kw)
            ids, dists = broken(np.asarray(ids), np.asarray(dists), queries)
            return ids, dists, rep
        monkeypatch.setattr(BatchedSearcher, "search", faulty)
    cell = tiny_cell(name)
    if cell.mix["loop"] == "open":
        cell.mix["rate_qps"] = 150     # calls of several queries
    out = harness.run_cell(cell, 77, 1.0, False, time.perf_counter())
    assert out["correct"] is False
    assert (out["checks"]["dist_err"]["value"] > 0 or out["failed"] > 0
            or out["checks"]["recall_miss"]["value"]
            > out["checks"]["recall_miss"]["limit"])
