"""The batched fetch-trace replay of the serving tier (`serve/ann.py`).

With the speculative window off, ``BatchedSearcher._account`` replays each
bucket's fetch trace one row at a time through ``LRUCache.replay``: one
tight pass over the row's real keys, NumPy for the rest. It must decide
exactly what the per-slot walk decided (a ``get``, a block read and a
``put`` per fetched list, in arrival order): every ``BatchReport`` counter,
every partition's statistics and recency order, every component's reads
and bytes, and every modeled latency to the bit.
"""
import numpy as np
import pytest

from repro.core.index import build_device_index
from repro.core.search.beam import SearchParams, SearchStats, search
from repro.core.search.engine import T_IO, rerank_tail_us
from repro.core.storage.blockstore import BlockStore, LRUCache
from repro.data.synthetic import make_queries, make_vector_dataset
from repro.serve.ann import BatchedSearcher, BatchReport, ServeConfig

ENTRY = 64                      # bytes per modeled LRU entry in these tests
TENANTS = ("a", "b", "c")


@pytest.fixture(scope="module")
def tiny_world():
    vecs = make_vector_dataset("prop-like", n=300, dim=16,
                               seed=3).astype(np.float32)
    index, _, _ = build_device_index(vecs, r=12, l_build=24, pq_m=4, seed=0)
    queries = make_queries("prop-like", 24, 16, seed=4).astype(np.float32)
    return vecs, index, queries


def _params(n, **kw):
    d = dict(l_size=24, beam_width=4, k=5, rerank_batch=5, r_max=12,
             universe=n, max_iters=32)
    d.update(kw)
    return SearchParams(**d)


def per_key_account(searcher, blocks, report, stats, count, caches,
                    components, key_offset=0, key_map=None, active=None):
    """The per-slot walk the batched replay replaced (speculative window
    off): for each fetched list in arrival order a ``get``, and on a miss
    one 4 KiB block read and a ``put``; each row priced in scalar Python."""
    trace = np.asarray(stats.fetch_trace)[:count]
    pq_ops = np.asarray(stats.pq_dists)[:count]
    list_hits = np.asarray(stats.list_hits)[:count]
    exact = np.asarray(stats.exact_dists)[:count]
    batches = np.asarray(stats.rerank_batches)[:count]
    lat = np.zeros(count)
    for qi in range(count):
        if active is not None and not active[qi]:
            continue
        cache, component = caches[qi], components[qi]
        misses = hits = io_rounds = 0
        for round_ids in trace[qi]:
            round_miss = 0
            for vid in round_ids:
                if vid < 0:
                    continue
                key = int(key_map[vid]) if key_map is not None \
                    else int(vid) + key_offset
                if cache.get(key) is not None:
                    hits += 1
                    continue
                blocks.read(component)
                misses += 1
                round_miss += 1
                cache.put(key, True)
            if round_miss:
                io_rounds += 1
        dec_ix = (misses + hits) if searcher.p.use_ef else 0
        dec_vec = int(exact[qi])
        report.graph_ios += misses
        report.cache_hits += hits
        report.vector_ios += int(exact[qi])
        report.pq_ops += int(pq_ops[qi])
        report.list_hits += int(list_hits[qi])
        report.exact_ops += int(exact[qi])
        report.decompressions += dec_ix + dec_vec
        report.io_rounds += io_rounds
        report.rerank_batches += int(batches[qi])
        io = io_rounds * T_IO
        cpu = (int(pq_ops[qi]) * searcher._t_pq
               + int(exact[qi]) * searcher._t_ex
               + dec_ix * searcher._t_dec_ix + dec_vec * searcher._t_dec_vec)
        lat[qi] = max(io, cpu) + min(io, cpu) * 0.1 + rerank_tail_us(
            batches[qi])
    return lat


def _random_stats(rng, count, iters, n_ids, bucket):
    """A bucket's host stats: rows stop after a random number of rounds
    (the rest ``-1``), some slots inside are ``-1`` too, and a small id
    space makes ids repeat within a round and across rounds."""
    trace = rng.integers(0, n_ids, (bucket, iters, 4)).astype(np.int32)
    trace[rng.random(trace.shape) < 0.2] = -1
    stop = rng.integers(0, iters + 1, bucket)
    trace[np.arange(iters)[None, :] >= stop[:, None]] = -1
    trace[0, 0, :] = trace[0, 0, 0]                 # one round of one id
    small = lambda hi: rng.integers(0, hi, bucket).astype(np.int32)
    return SearchStats(iters=stop.astype(np.int32), lists_fetched=None,
                       prefetch_iter=None, rerank_batches=small(5),
                       exact_dists=small(60), pq_dists=small(900),
                       list_hits=small(300), fetch_trace=trace,
                       hint_trace=None)


def _store(capacity, shared, floors):
    """One BlockStore like the searcher's: a shard partition and three
    tenant partitions of ``capacity`` entries each (pooled if shared)."""
    blocks = BlockStore(cache_bytes=capacity * ENTRY, shared_budget=shared)
    blocks.register_cache("shard0", ENTRY)
    for t in TENANTS:
        blocks.register_tenant_cache(t, ENTRY,
                                     floor_bytes=floors * ENTRY if shared
                                     else 0)
    return blocks


def _state(blocks):
    return dict(
        parts={n: (c.hits, c.misses, c.prefetch_hits, c.lookups,
                   list(c._d), dict(c._tick))
               for n, c in blocks.partitions.items()},
        comps={n: s.snapshot() for n, s in blocks.components.items()},
        total=blocks.io.snapshot(),
        clock=blocks.budget._clock if blocks.budget is not None else None)


# (capacity in entries, shared budget, tenants, key translation, active
# mask, use_ef, traces from the device search)
CASES = {
    "padding_and_repeats": (64, False, False, None, False, True, False),
    "evicting": (7, False, False, None, False, True, False),
    "capacity_0": (0, False, False, None, False, True, False),
    "key_offset": (16, False, False, "offset", False, True, False),
    "key_map": (16, False, False, "map", False, True, False),
    "active_mask": (16, False, False, None, True, True, False),
    "no_ef": (16, False, False, None, False, False, False),
    "tenants": (12, False, True, "offset", False, True, False),
    "tenants_shared_budget": (12, True, True, "map", True, True, False),
    "shard_shared_budget": (9, True, False, None, False, True, False),
    "device_traces": (40, False, False, None, False, True, True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_batched_replay_equals_per_key_walk(tiny_world, case):
    cap, shared, tenants, keys, use_active, use_ef, device = CASES[case]
    vecs, index, queries = tiny_world
    rng = np.random.default_rng(sorted(CASES).index(case))
    searcher = BatchedSearcher(index, _params(len(vecs), use_ef=use_ef))
    n_ids = 40
    key_map = rng.permutation(10 * n_ids)[:n_ids].astype(np.int64) + 5
    old, new = _store(cap, shared, 2), _store(cap, shared, 2)
    rep_old, rep_new = BatchReport(), BatchReport()
    served = 0
    for b, (count, bucket) in enumerate([(8, 8), (5, 8), (1, 1), (8, 8)]):
        if device:
            q = queries[3 * b:3 * b + bucket]
            stats = SearchStats(*(None if x is None else np.asarray(x)
                                  for x in search(index, q, searcher.p)[2]))
        else:
            stats = _random_stats(rng, count, 12, n_ids, bucket)
        active = rng.random(count) < 0.6 if use_active else None
        rows = [TENANTS[i] for i in rng.integers(0, 3, count)] \
            if tenants else None
        args = dict(key_offset=100 * b if keys == "offset" else 0,
                    key_map=key_map if keys == "map" else None,
                    active=active)
        lats = []
        for blocks, rep, account in [
                (old, rep_old,
                 lambda *a, **kw: per_key_account(searcher, old, *a, **kw)),
                (new, rep_new, searcher._account)]:
            searcher.blocks = blocks
            if rows is None:
                caches = [blocks.partitions["shard0"]] * count
                comps = ["shard0"] * count
            else:
                caches = [blocks.partitions[f"tenant:{t}"] for t in rows]
                comps = [f"tenant:{t}" for t in rows]
            lats.append(account(rep, stats, count, caches, comps, **args))
        assert lats[1].dtype == lats[0].dtype == np.float64
        np.testing.assert_array_equal(lats[1].view(np.uint64),
                                      lats[0].view(np.uint64))
        served += count if active is None else int(active.sum())
    assert rep_new.replay_rows_batched == served
    rep_new.replay_rows_batched = 0
    assert vars(rep_new) == vars(rep_old)
    assert _state(new) == _state(old)
    if cap > 0:
        assert rep_new.graph_ios > 0 and rep_new.io_rounds > 0
    if case in ("padding_and_repeats", "device_traces"):
        assert rep_new.cache_hits > 0


def _lru_pair(capacity, shared):
    """Two identical setups: one or (shared) two partitions."""
    out = []
    for _ in range(2):
        if shared:
            blocks = BlockStore(cache_bytes=capacity * ENTRY,
                                shared_budget=True)
            out.append([blocks.register_cache(
                "x", ENTRY, floor_bytes=ENTRY if capacity > 0 else 0),
                        blocks.register_cache("y", ENTRY)])
        else:
            out.append([LRUCache(capacity, ENTRY)])
    return out


@pytest.mark.parametrize("capacity,shared", [
    (0, False), (-1, False), (1, False), (6, False), (500, False),
    (6, True), (0, True)])
def test_lru_replay_equals_get_then_put(capacity, shared):
    """``replay`` gives the flags, counters, recency order and (pooled)
    recency ticks of ``get``, then ``put(key, True)`` on a miss."""
    rng = np.random.default_rng(capacity + 2 + 1000 * shared)
    old, new = _lru_pair(capacity, shared)
    for step in range(12):
        part = step % len(old)
        keys = rng.integers(0, 20, rng.integers(0, 30)).tolist()
        want = []
        for k in keys:
            v = old[part].get(k)
            want.append(v is not None)
            if v is None:
                old[part].put(k, True)
        got = new[part].replay(keys)
        assert got.dtype == bool and got.tolist() == want
        for a, b in zip(old, new):
            assert (a.hits, a.misses, a.lookups, list(a._d), a._tick) == (
                b.hits, b.misses, b.lookups, list(b._d), b._tick)
            if shared:
                assert a.budget._clock == b.budget._clock
    assert sum(c.lookups for c in new) > 0


@pytest.mark.parametrize("nq", [1, 9, 20])
def test_replay_rows_batched_counts_served_rows(tiny_world, nq):
    """The default config replays every served row in one pass (pad rows
    are not replayed); the speculative window keeps the per-key walk."""
    vecs, index, queries = tiny_world
    p = _params(len(vecs))
    _, _, rep = BatchedSearcher(index, p).search(queries[:nq])
    assert rep.replay_rows_batched == nq
    assert rep.graph_ios + rep.cache_hits > 0
    _, _, rep = BatchedSearcher(
        index, p, ServeConfig(prefetch_depth=4)).search(queries[:nq])
    assert rep.replay_rows_batched == 0
    assert rep.graph_ios + rep.cache_hits + rep.prefetch_hits > 0
