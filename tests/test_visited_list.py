"""The exact visited set of the device traversal (`core/search/beam.py`).

``traverse`` scores a round's neighbors only if they are not already in the
row's candidate list, with an "expanded" flag per list slot. It must walk
exactly as a beam search that remembers every id it ever scored: the same
lists, distances, frontiers, rounds and fetches, bit for bit. The reference
below is that search, written in NumPy and Python sets, with the merge's
tie-breaking spelled out (equal distances: list entries first, then new ids
in ascending order).

A second check keeps an O(n) set from coming back: no carry of the
traversal's ``while_loop`` may have a dimension of n.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph.pq import build_lut_jnp
from repro.core.index import build_device_index
from repro.core.search.beam import (DeviceIndex, SearchParams, search_batched,
                                    traverse)
from repro.data.synthetic import make_queries, make_vector_dataset
from repro.kernels.dispatch import KernelConfig

L, W, R, K, B = 16, 2, 10, 5, 5
MAX_ITERS = 10            # cuts the rows that would walk longer


@pytest.fixture(scope="module")
def dup_world():
    """A graph over rows of which a third are exact copies of others: equal
    PQ codes give equal distances, so the merge breaks ties all the time."""
    base = make_vector_dataset("prop-like", n=180, dim=16, seed=11)
    vecs = np.concatenate([base, base[::2][:90]]).astype(np.float32)
    index, _, _ = build_device_index(vecs, r=R, l_build=24, pq_m=4, seed=1)
    queries = make_queries("prop-like", 32, 16, seed=12).astype(np.float32)
    return index, queries


def _adc(codes: np.ndarray, lut: np.ndarray) -> np.ndarray:
    """[n, M] codes x [M, K] LUT -> [n] float32, summed left to right."""
    acc = lut[0, codes[:, 0]]
    for m in range(1, codes.shape[1]):
        acc = acc + lut[m, codes[:, m]]
    return acc


def reference(adj: list, dist: np.ndarray, entry: int, max_iters: int):
    """One query's beam search over a set of every id it has scored.
    ``dist`` holds its PQ distance to every row. Returns the final list and
    the counters ``traverse`` reports, plus what the dense set scored and
    whether the row was cut by ``max_iters`` and met a tie."""
    kb = min(K + B, L)
    cand = [entry]                       # ids in slot order, best first
    visited, expanded = {entry}, set()
    iters = fetched = hits = stab = 0
    scored, dense_scored, pf_iter = 1, 1, -1
    prev_top, trace, tie = [-1] * kb, [], False
    while True:
        frontier = [i for i in cand if i not in expanded]
        if not frontier or iters >= max_iters:
            break
        # list slots are in distance order, ties by slot: a stable sort
        sel = sorted(frontier, key=lambda i: dist[i])[:W]
        expanded.update(sel)
        fetched += len(sel)
        trace.append(sel + [-1] * (W - len(sel)))
        nbrs = sorted({int(j) for i in sel for j in adj[i]})
        listed = set(cand)
        hits += sum(j in listed for j in nbrs)
        # the entry, marked visited from the start, is never scored again
        scored += sum(j not in listed and j != entry for j in nbrs)
        new = [j for j in nbrs if j not in visited]
        visited.update(new)
        dense_scored += len(new)
        merged = cand + new
        md = np.array([dist[i] for i in merged], np.float32)
        tie |= len(np.unique(md)) < len(md)
        cand = [merged[i] for i in np.argsort(md, kind="stable")[:L]]
        top = sorted(cand[:kb] + [-1] * (kb - len(cand[:kb])))
        stab = stab + W if top == prev_top else 0
        if stab >= B and pf_iter < 0:
            pf_iter = iters + 1
        iters += 1
        prev_top = top
    cut = iters >= max_iters and any(i not in expanded for i in cand)
    ids = np.array(cand + [-1] * (L - len(cand)), np.int32)
    d = np.array([dist[i] for i in cand] + [np.inf] * (L - len(cand)),
                 np.float32)
    tr = np.full((max_iters, W), -1, np.int32)
    if trace:
        tr[:len(trace)] = trace
    return dict(ids=ids, d=d, iters=iters, fetched=fetched, pf_iter=pf_iter,
                scored=scored, list_hits=hits, trace=tr,
                dense_scored=dense_scored, cut=cut, tie=tie)


@jax.jit
def _luts(index, queries):
    return jax.vmap(lambda q: build_lut_jnp(q, index.pq_centroids))(queries)


# The LUTs come in as one array: the reference scores with the same values.
_traverse = jax.jit(traverse, static_argnames=("p",))


@pytest.mark.parametrize("fused", [False, True], ids=["unfused", "fused"])
@pytest.mark.parametrize("nq", [1, 8, 32])
def test_traverse_equals_scored_set_search(dup_world, nq, fused):
    index, queries = dup_world
    kernels = KernelConfig("ref", "ref", "ref", "ref",
                           "pallas-interpret" if fused else "off")
    p = SearchParams(l_size=L, beam_width=W, k=K, rerank_batch=B, r_max=R,
                     universe=int(index.pq_codes.shape[0]),
                     max_iters=MAX_ITERS, trace_fetches=True, kernels=kernels)
    # Row 0 is one that max_iters cuts.
    q = queries[:nq]
    luts = _luts(index, jnp.asarray(q))
    ids, d, (iters, fetched, pf_iter, pq, hits, trace, _) = \
        _traverse(index, luts, p=p)
    luts = np.asarray(luts)
    codes = np.asarray(index.pq_codes).astype(np.int64)
    nbrs, counts = np.asarray(index.neighbors), np.asarray(index.counts)
    adj = [nbrs[i, :counts[i]] for i in range(len(codes))]
    refs = [reference(adj, _adc(codes, luts[r]), int(index.medoid),
                      MAX_ITERS) for r in range(nq)]
    np.testing.assert_array_equal(np.asarray(ids),
                                  np.stack([r["ids"] for r in refs]))
    np.testing.assert_array_equal(np.asarray(d).view(np.uint32),
                                  np.stack([r["d"] for r in refs])
                                  .view(np.uint32))
    for name, got in [("iters", iters), ("fetched", fetched),
                      ("pf_iter", pf_iter), ("scored", pq),
                      ("list_hits", hits)]:
        np.testing.assert_array_equal(np.asarray(got),
                                      [r[name] for r in refs], err_msg=name)
    np.testing.assert_array_equal(np.asarray(trace),
                                  np.stack([r["trace"] for r in refs]))
    # The world exercises what the claim rests on: rows cut by max_iters,
    # ties in the merge, ids scored again after the list dropped them.
    assert any(r["cut"] for r in refs)
    assert any(r["tie"] for r in refs)
    assert any(r["scored"] > r["dense_scored"] for r in refs)
    assert all(r["list_hits"] > 0 for r in refs)
    if nq > 1:
        assert not all(r["cut"] for r in refs)


N_ODD = 4099              # equal to no width, list size or round count


@pytest.mark.parametrize("use_ef", [True, False], ids=["ef", "raw"])
def test_no_traversal_carry_grows_with_n(use_ef):
    """Trace ``search_batched`` at an n that equals no other dimension and
    look at every ``while_loop`` in it: only the index leaves (loop
    constants) may have a dimension of n, never a carry."""
    from repro.core.codec.elias_fano import slot_layout
    n, m, d, nq = N_ODD, 8, 32, 16
    words = slot_layout(R, n)[3]
    f = jax.ShapeDtypeStruct
    index = DeviceIndex(
        neighbors=f((n, R), jnp.int32), counts=f((n,), jnp.int32),
        ef_slots=f((n, words), jnp.uint32), pq_codes=f((n, m), jnp.uint8),
        pq_centroids=f((m, 256, d // m), jnp.float32),
        vectors=f((n, d), jnp.float32), medoid=f((), jnp.int32))
    p = SearchParams(l_size=48, beam_width=4, k=10, rerank_batch=10,
                     r_max=R, universe=n, use_ef=use_ef, max_iters=40,
                     trace_fetches=True, trace_hints=True,
                     kernels=KernelConfig("ref", "ref", "ref", "ref", "ref"))
    jaxpr = jax.make_jaxpr(functools.partial(search_batched, p=p))(
        index, f((nq, d), jnp.float32))

    def loops(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "while":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from loops(sub)

    found = list(loops(jaxpr.jaxpr))
    assert len(found) >= 2                      # traversal and re-rank
    consts_n = False
    for eqn in found:
        ncon = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
        carries = [v.aval.shape for v in eqn.invars[ncon:]]
        assert all(n not in s for s in carries), carries
        consts_n |= any(n in v.aval.shape for v in eqn.invars[:ncon])
    assert consts_n              # the index leaves do reach the loop
