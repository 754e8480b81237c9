"""Batched multi-tenant serving path (`repro.serve.ann`).

The two contracts the serving tier must keep (ISSUE 2 acceptance):
(a) batching is invisible — a bucketed/padded batch returns exactly what
    per-query (nq=1) search returns, including ragged final buckets;
(b) shard fan-out + global top-K merge returns exactly the unsharded top-K
    when every path is run exhaustively (L >= n per shard, benefit test
    disabled), so the merge itself is lossless.
"""
import numpy as np
import pytest

from repro.core.distributed.sharded_index import build_sharded_index
from repro.core.index import build_device_index
from repro.core.search.beam import SearchParams, search, search_vmapped
from repro.data.synthetic import ground_truth, make_queries, make_vector_dataset
from repro.serve.ann import BatchedSearcher, ServeConfig, plan_buckets


@pytest.fixture(scope="module")
def small_world():
    vecs = make_vector_dataset("prop-like", n=700, dim=16,
                               seed=0).astype(np.float32)
    index, graph, cb = build_device_index(vecs, r=16, l_build=32, pq_m=4,
                                          seed=0)
    queries = make_queries("prop-like", 32, 16).astype(np.float32)
    return vecs, index, queries


def _params(n, **kw):
    d = dict(l_size=32, beam_width=4, k=5, rerank_batch=5, r_max=16,
             universe=n, max_iters=64)
    d.update(kw)
    return SearchParams(**d)


def test_plan_buckets():
    assert plan_buckets(7, (1, 8, 32)) == [(0, 7, 8)]
    assert plan_buckets(32, (1, 8, 32)) == [(0, 32, 32)]
    assert plan_buckets(71, (1, 8, 32)) == [(0, 32, 32), (32, 32, 32),
                                            (64, 7, 8)]
    assert plan_buckets(1, (1, 8, 32)) == [(0, 1, 1)]
    # A tail whose covering bucket wastes more rows than the tail itself is
    # decomposed into smaller full buckets instead of padded (9 -> 8 + 1).
    assert plan_buckets(9, (1, 8, 32)) == [(0, 8, 8), (8, 1, 1)]
    assert plan_buckets(3, (8, 32)) == [(0, 3, 8)]   # nothing fits: pad
    # The old rule silently padded any tail to its covering bucket: a
    # 17-query batch became 32 rows (15 wasted). Padding is now weighed
    # against the dispatch cost of peeling: 17 -> 8 + 8 + 1, zero padding.
    assert plan_buckets(17, (1, 8, 32)) == [(0, 8, 8), (8, 8, 8), (16, 1, 1)]
    assert plan_buckets(33, (1, 8, 32)) == [(0, 32, 32), (32, 1, 1)]
    with pytest.raises(ValueError):
        plan_buckets(4, (0,))


def test_plan_buckets_overflow_explicit():
    """max_chunks makes the dispatch bound explicit: a plan needing more
    chunks raises instead of silently growing."""
    assert plan_buckets(71, (1, 8, 32), max_chunks=3) == [
        (0, 32, 32), (32, 32, 32), (64, 7, 8)]
    with pytest.raises(ValueError, match="max_chunks"):
        plan_buckets(71, (1, 8, 32), max_chunks=2)
    with pytest.raises(ValueError, match="max_chunks"):
        plan_buckets(17, (1, 8, 32), max_chunks=2)   # 8+8+1 needs 3


@pytest.mark.parametrize("nq", [1, 7, 32])
def test_batched_equals_per_query(small_world, nq):
    """(a): B in {1, 7, 32} through pad-and-bucket serving == nq=1 search.
    nq=7 exercises the ragged final bucket (padded up to 8)."""
    vecs, index, queries = small_world
    p = _params(len(vecs))
    searcher = BatchedSearcher(index, p, ServeConfig(buckets=(1, 8, 32)))
    ids, dists, report = searcher.search(queries[:nq])
    assert ids.shape == (nq, p.k)
    for qi in range(nq):
        i1, d1, _ = search(index, queries[qi][None], searcher.p)
        np.testing.assert_array_equal(ids[qi], np.asarray(i1)[0])
        np.testing.assert_array_equal(dists[qi], np.asarray(d1)[0])


def test_direct_batch_equals_per_query(small_world):
    """The device batch program itself (no serving layer) is row-exact."""
    vecs, index, queries = small_world
    p = _params(len(vecs))
    ids, dists, stats = search(index, queries, p)
    for qi in [0, 13, 31]:
        i1, d1, s1 = search(index, queries[qi][None], p)
        np.testing.assert_array_equal(np.asarray(ids)[qi], np.asarray(i1)[0])
        np.testing.assert_array_equal(np.asarray(dists)[qi],
                                      np.asarray(d1)[0])
        assert int(np.asarray(stats.iters)[qi]) == int(s1.iters[0])
        assert int(np.asarray(stats.exact_dists)[qi]) == int(s1.exact_dists[0])


def test_vmapped_matches_batched(small_world):
    """The legacy vmap formulation and the hand-batched loop agree."""
    vecs, index, queries = small_world
    p = _params(len(vecs))
    ids_b, d_b, _ = search(index, queries[:8], p)
    ids_v, d_v, _ = search_vmapped(index, queries[:8], p)
    np.testing.assert_array_equal(np.asarray(ids_b), np.asarray(ids_v))
    np.testing.assert_allclose(np.asarray(d_b), np.asarray(d_v))


def test_sharded_merge_equals_unsharded(small_world):
    """(b): with exhaustive search (L >= shard n, benefit test off), the
    2-shard fan-out + global top-K merge == unsharded top-K == brute force,
    ids and distances."""
    vecs, _, _ = small_world
    sub = vecs[:240]                       # 2 shards x 120, no padding
    queries = make_queries("prop-like", 16, 16).astype(np.float32)
    gt = ground_truth(sub, queries, k=5)

    # Exhaustive settings: the candidate list can hold every vertex and
    # re-ranking covers it fully, so graph search degenerates to exact.
    exh = dict(l_size=256, beam_width=4, k=5, rerank_batch=16,
               benefit_threshold=0.0, max_rerank_batches=32, r_max=24,
               max_iters=256)

    un_index, _, _ = build_device_index(sub, r=24, l_build=48, pq_m=4, seed=0)
    p_un = SearchParams(universe=len(sub), **exh)
    un = BatchedSearcher(un_index, p_un, ServeConfig(buckets=(16,)))
    ids_un, d_un, _ = un.search(queries)

    sh_index, per = build_sharded_index(sub, 2, r=24, l_build=48, pq_m=4)
    p_sh = SearchParams(universe=per, **exh)
    sh = BatchedSearcher(sh_index, p_sh, ServeConfig(buckets=(16,)),
                         shard_size=per)
    ids_sh, d_sh, rep = sh.search(queries)

    assert rep.n_shards == 2
    np.testing.assert_array_equal(ids_un, gt)      # both paths are exact
    np.testing.assert_array_equal(ids_sh, gt)
    np.testing.assert_allclose(d_sh, d_un, rtol=1e-6)
    assert ids_sh.max() >= per                     # ids from shard 1 present


def test_io_accounting(small_world):
    """The admission layer replays fetch traces through the §3.4 LRU: a
    repeated identical batch must be (mostly) cache hits, and the counters
    must be internally consistent."""
    vecs, index, queries = small_world
    p = _params(len(vecs))
    searcher = BatchedSearcher(index, p, ServeConfig(buckets=(8,),
                                                     cache_bytes=1 << 20))
    _, _, r1 = searcher.search(queries[:8])
    assert r1.graph_ios > 0
    assert r1.vector_ios == r1.exact_ops > 0
    assert r1.io_rounds > 0 and r1.modeled_latency_us > 0
    _, _, r2 = searcher.search(queries[:8])
    assert r2.graph_ios == 0                       # cache is warm now
    assert r2.cache_hits >= r1.graph_ios


def test_stats_disabled_path(small_world):
    """account_io=False serves without tracing (empty trace, no replay)."""
    vecs, index, queries = small_world
    p = _params(len(vecs))
    searcher = BatchedSearcher(index, p,
                               ServeConfig(buckets=(8,), account_io=False))
    ids, dists, rep = searcher.search(queries[:8])
    assert rep.graph_ios == 0 and rep.modeled_latency_us == 0
    ids_ref, _, _ = search(index, queries[:8], p)
    np.testing.assert_array_equal(ids, np.asarray(ids_ref))


# --------------------------------------------------------------------------
# Live-updatable serving: BatchedSearcher over a SnapshotHandle (§3.5)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def live_world():
    from repro.core.graph.pq import encode_pq, train_pq
    from repro.core.graph.vamana import build_vamana
    from repro.core.storage.vector_store import (DecoupledVectorStore,
                                                 StoreConfig)
    from repro.core.update.fresh import StreamingIndex, UpdateConfig
    vecs = make_vector_dataset("prop-like", n=400, dim=16,
                               seed=2).astype(np.float32)
    graph = build_vamana(vecs, r=16, l_build=32, seed=0)
    cb = train_pq(vecs, m=4, seed=0)
    codes = encode_pq(vecs, cb)
    vs = DecoupledVectorStore(StoreConfig(dim=16, dtype=np.float32,
                                          segment_capacity=256))
    vs.append(np.arange(len(vecs)), vecs)
    vs.seal_active()
    idx = StreamingIndex(graph.adjacency, graph.medoid, vs, codes, cb,
                         UpdateConfig(r=16, l_build=32,
                                      merge_threshold=10**9))
    return vecs, idx


def test_live_searcher_matches_streaming_search(live_world):
    """The serving tier over a SnapshotHandle returns exactly what the
    update tier's own snapshot search returns (one engine, two callers)."""
    vecs, idx = live_world
    searcher = BatchedSearcher(idx.handle,
                               SearchParams(l_size=32, k=5, rerank_batch=5,
                                            max_iters=64,
                                            benefit_threshold=0.0),
                               ServeConfig(buckets=(4, 8)))
    queries = vecs[[3, 50, 90, 123, 200]] + 0.001
    ids, dists, rep = searcher.search(queries)
    ref_ids, ref_d = idx.search_batch(queries, k=5, l_size=32)
    np.testing.assert_array_equal(ids, ref_ids)
    assert rep.snapshot_version == idx.handle.current().version


def test_live_searcher_hot_swaps_on_publish(live_world):
    """Each batch pins the snapshot current at admission; a merge between
    batches is picked up (version moves), tombstones/memtable included."""
    vecs, idx = live_world
    searcher = BatchedSearcher(idx.handle,
                               SearchParams(l_size=32, k=5, rerank_batch=5,
                                            max_iters=64,
                                            benefit_threshold=0.0),
                               ServeConfig(buckets=(4,)))
    q = vecs[[60, 61, 62, 63]]
    ids0, _, rep0 = searcher.search(q)
    v0 = rep0.snapshot_version
    target = int(ids0[0, 0])
    idx.delete([target])
    fresh = vecs[60] * 1.0002
    idx.insert(np.array([len(idx.adjacency) + 10]), fresh[None])
    fresh_id = len(idx.adjacency) + 10
    ids1, _, rep1 = searcher.search(q)
    assert rep1.snapshot_version == v0          # no publish yet
    assert target not in set(ids1.reshape(-1).tolist())   # tombstone masked
    assert fresh_id in set(ids1[0].tolist())    # memtable side-scan
    assert rep1.mem_candidates == 1
    idx.merge()
    ids2, _, rep2 = searcher.search(q)
    assert rep2.snapshot_version == v0 + 1      # hot swap on publish
    assert target not in set(ids2.reshape(-1).tolist())
    assert fresh_id in set(ids2[0].tolist())    # now served from the graph
    assert rep2.mem_candidates == 0


@pytest.mark.parametrize("nq", [1, 7, 12])
def test_round_counters_match_iters(small_world, nq):
    """rounds / row_rounds / slot_rounds are read off SearchStats.iters of
    each launch, pad rows included: nq=7 pads one row, nq=12 runs as 8+1+
    1+1+1."""
    vecs, index, queries = small_world
    p = _params(len(vecs))
    searcher = BatchedSearcher(index, p, ServeConfig(buckets=(1, 8, 32)))
    _, _, rep = searcher.search(queries[:nq])
    rounds = row_rounds = slot_rounds = 0
    for start, count, bucket in plan_buckets(nq, (1, 8, 32)):
        q = queries[start:start + count]
        q = np.concatenate([q, np.repeat(q[-1:], bucket - count, 0)])
        iters = np.asarray(search(index, q, searcher.p)[2].iters)
        rounds += int(iters.max())
        row_rounds += int(iters.sum())
        slot_rounds += int(iters.max()) * bucket
    assert (rep.rounds, rep.row_rounds, rep.slot_rounds) == (
        rounds, row_rounds, slot_rounds)
    assert 0 < rep.row_rounds <= rep.slot_rounds
    if nq == 1:
        assert rep.row_rounds == rep.rounds == rep.slot_rounds
    if nq == 7:     # the pad row repeats query 6: its rounds count again
        real = np.asarray(search(index, queries[:7], searcher.p)[2].iters)
        assert rep.row_rounds == int(real.sum()) + int(real[6])


def test_search_spans_nest_in_the_callers_window(small_world, tmp_path):
    """A CPU-recorded trace of one call holds ``serve.search`` around
    ``serve.plan``, ``serve.launch``, ``serve.fetch``, ``serve.account`` and
    ``serve.merge``, read with the caller's own span, on one clock."""
    import jax

    vecs, index, queries = small_world
    searcher = BatchedSearcher(index, _params(len(vecs)),
                               ServeConfig(buckets=(1, 8)))
    searcher.search(queries[:9])                # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        searcher.search(queries[:9])            # runs as 8 + 1
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))[-1]
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "bench.")):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)))
    window, = spans["bench.window"]
    (s0, s1, meta), = spans["serve.search"]
    assert meta == {"call": 2, "nq": 9}
    assert window[0] <= s0 and s1 <= window[1]
    for name, n in [("serve.plan", 1), ("serve.launch", 2),
                    ("serve.fetch", 2), ("serve.account", 2),
                    ("serve.merge", 1)]:
        assert len(spans[name]) == n, name
        assert all(s0 <= a and b <= s1 for a, b, _ in spans[name]), name
    assert [m for *_, m in sorted(spans["serve.launch"])] == [
        {"bucket": 8, "count": 8}, {"bucket": 1, "count": 1}]
