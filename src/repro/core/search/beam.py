"""Device-side graph beam search (`jax.lax.while_loop`) + latency-aware
re-ranking (paper §3.4), batch-first over queries.

Faithful mapping of the paper's search path:

- Traversal touches ONLY the auxiliary index (Elias-Fano slots or raw
  adjacency) + in-HBM PQ codes — never full-precision vectors. In the paper
  this is a runtime scheduling decision; here it is a *compile-time program
  property* (the traversal while_loop simply has no dependence on the vector
  store).
- Phase 1 prefetch trigger: once the top-(K+B) heap survives B consecutive
  expansions unchanged, the top-K candidate set is frozen as the prefetch set
  (§3.4 "stability"); we record the trigger iteration for the I/O model.
- Phase 2 re-rank: batches of B exact distances, early-terminated when the
  *benefit ratio* (fraction of a batch entering the top-K) drops below the
  threshold (default 0.01).

Batch-first: every public entry point takes queries of shape [nq, d] and the
whole batch advances through ONE `while_loop` whose carries carry a leading
query axis; finished rows are frozen by masking their updates. Single-query
search is the nq=1 case (`search_one`). This is deliberately NOT
`vmap(single_query_search)`: vmap of a `while_loop` re-selects every carry
each round, which costs O(nq * n) on the dense visited arrays alone, while
the hand-batched loop only touches what each round writes. The old vmapped
formulation is kept as `search_vmapped` — it is the measured baseline that
`benchmarks/bench_serve_ann.py` compares against.

The uncompressed-adjacency variant exists for the paper's ablation (Exp#1
"Decouple" / "DecoupleSearch" arms). The compute stages — batched PQ ADC,
EF slot decode, exact re-rank — go through the kernel dispatch layer
(`repro.kernels.dispatch`, docs/KERNELS.md): `SearchParams.kernels` names a
backend per op (`ref` jnp oracle / `pallas` TPU kernel /
`pallas-interpret`), resolved once at config time (`resolve_kernels`), so
the same program runs on CPU tests and TPU with zero trace-time platform
checks.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.dispatch import KernelConfig

from ..graph.pq import build_lut_jnp


class DeviceIndex(NamedTuple):
    """HBM-resident search state (one shard)."""
    neighbors: jnp.ndarray      # [n, R] int32 (-1 padded) — raw variant
    counts: jnp.ndarray         # [n] int32
    ef_slots: jnp.ndarray       # [n, slot_words] uint32 — compressed variant
    pq_codes: jnp.ndarray       # [n, M] uint8
    pq_centroids: jnp.ndarray   # [M, K, dsub] f32
    vectors: jnp.ndarray        # [n, d] full precision (re-rank tier)
    medoid: jnp.ndarray         # scalar int32
    tombstone: jnp.ndarray = None  # [n] bool — §3.5 live-snapshot deletes;
                                # None for frozen indexes (an empty pytree
                                # node, so frozen programs are unchanged).
                                # Masked in rerank when
                                # SearchParams.filter_tombstones is set:
                                # traversal still routes THROUGH deleted
                                # vertices (graph connectivity is repaired
                                # only at merge), they are just never
                                # returned.


class SearchParams(NamedTuple):
    l_size: int = 64            # candidate list size L
    beam_width: int = 4         # W
    k: int = 10                 # result set size K
    rerank_batch: int = 10      # B (also prefetch stability threshold)
    benefit_threshold: float = 0.01
    max_iters: int = 256
    max_rerank_batches: int = 16
    use_ef: bool = True         # compressed index traversal
    r_max: int = 32
    universe: int = 0           # vector-id universe for EF slots (0 -> n)
    visited_hash_bits: int = 0  # >0: open-addressing visited set of 2^bits
                                # slots instead of [n]-bool arrays (§Perf B)
    trace_fetches: bool = False  # record the per-round adjacency-fetch ids so
                                 # the serving tier can replay them through
                                 # the §3.4 LRU / I/O model (serve/ann.py)
    trace_hints: bool = False    # also record each round's PROVISIONAL next
                                 # frontier (top-W unexpanded candidates
                                 # before the round's neighbors merge) — the
                                 # honest lossy predictor the serving tier's
                                 # speculative prefetch issues from
    kernels: KernelConfig | None = None  # per-op compute backend (dispatch
                                 # layer); None -> REPRO_KERNELS env default.
                                 # Resolve at config time (resolve_kernels).
    filter_tombstones: bool = False  # live-snapshot mode (§3.5): mask
                                 # index.tombstone rows out of the re-rank
                                 # heap (id -> -1), never out of traversal.


class SearchStats(NamedTuple):
    iters: jnp.ndarray             # [nq] traversal rounds (graph I/O batches)
    lists_fetched: jnp.ndarray     # [nq] adjacency lists read from the index tier
    prefetch_iter: jnp.ndarray     # [nq] iteration prefetch triggered (-1: never)
    rerank_batches: jnp.ndarray    # [nq] re-rank batches actually executed
    exact_dists: jnp.ndarray       # [nq] full-precision distance computations
    pq_dists: jnp.ndarray          # [nq] PQ (ADC) distance computations
    fetch_trace: jnp.ndarray       # [nq, max_iters, W] fetched vertex ids
                                   # (-1 = none; empty unless trace_fetches)
    hint_trace: jnp.ndarray        # [nq, max_iters, W] provisional next-
                                   # frontier ids recorded DURING round r as
                                   # the speculation for round r+1 (-1 =
                                   # none; empty unless trace_hints)


def resolve_kernels(p: SearchParams, platform: str | None = None,
                    shapes: dict | None = None) -> SearchParams:
    """Fill ``p.kernels`` with a concrete per-op backend config.

    This is the single config-time resolution point: ``None`` takes the
    ``REPRO_KERNELS`` env default, ``auto`` entries resolve for
    ``platform`` (default: the process backend), ``auto-tuned`` entries
    resolve per (op, shape-bucket) from the persisted autotune cache
    (pass ``shapes`` — op name -> dims dict — when the caller knows the
    serving shapes; without it the op's majority-winner bucket decides),
    and a raw ``pallas`` request degrades to the interpreter off-TPU.
    Public entry points call it before jit, so no backend checks survive
    into (or run during) tracing; a caller composing ``search_batched``
    inside its own jit/shard_map (e.g. ``make_sharded_search``) should
    call it when the program is built, passing the mesh's platform.
    """
    k = p.kernels
    k = (dispatch.from_env(platform=platform) if k is None
         else k.resolve(platform, shapes))
    return p if k == p.kernels else p._replace(kernels=k)


def _hash_slots(ids, bits: int):
    h = (ids.astype(jnp.uint32) * jnp.uint32(2654435761))
    return (h >> jnp.uint32(32 - bits)).astype(jnp.int32)


def _gather_neighbors(index: DeviceIndex, sel_ids: jnp.ndarray,
                      p: SearchParams, n: int) -> jnp.ndarray:
    """[nq, W] vertex ids -> [nq, W * r_max] neighbor ids (-1 = invalid)."""
    nq = sel_ids.shape[0]
    valid_sel = sel_ids >= 0
    safe = jnp.clip(sel_ids, 0, n - 1)
    if p.use_ef:
        universe = p.universe or n
        vals, cnts = dispatch.ef_decode(index.ef_slots[safe.reshape(-1)],
                                        p.r_max, universe, p.kernels)
        j = jnp.arange(p.r_max, dtype=jnp.int32)
        nbrs = jnp.where(j[None, :] < cnts[:, None], vals, -1)
        nbrs = nbrs.reshape(safe.shape + (p.r_max,))
    else:
        nbrs = index.neighbors[safe]
    nbrs = jnp.where(valid_sel[..., None], nbrs, -1)
    return nbrs.reshape(nq, -1)


def _adc_batch(codes: jnp.ndarray, luts: jnp.ndarray,
               kernels: KernelConfig | None) -> jnp.ndarray:
    """[nq, m, M] codes x [nq, M, K] per-query LUTs -> [nq, m] distances
    (the batched pq_adc op: jnp gather-sum or the Pallas table select)."""
    return dispatch.pq_adc_batched(codes, luts, kernels)


def traverse(index: DeviceIndex, luts: jnp.ndarray, p: SearchParams):
    """Batched beam traversal: per-query LUTs [nq, M, K] ->
    (cand_ids [nq, L], cand_d [nq, L], (iters, fetched, pf_iter, pq, trace)).

    One while_loop advances the whole batch; a row with no unexpanded
    frontier (or out of iterations) is *frozen*: its frontier distances are
    masked to +inf so it selects nothing, fetches nothing, and its candidate
    list / counters pass through unchanged. Each row's trajectory is
    therefore identical to what a solo (nq=1) run produces — the equality
    `tests/test_serve_ann.py` asserts.

    Two visited-set representations (§Perf iteration B):
    - dense [nq, n]-bool arrays (exact; O(n) HBM per query), or
    - a 2^visited_hash_bits open-addressing fingerprint table plus
      per-list-slot expansion flags (O(2^bits); a hash eviction can only
      cause a re-visit — extra work, never a wrong result).
    """
    n = index.pq_codes.shape[0]
    nq = luts.shape[0]
    L, W = p.l_size, p.beam_width
    KB = min(p.k + p.rerank_batch, L)
    use_hash = p.visited_hash_bits > 0
    rows = jnp.arange(nq, dtype=jnp.int32)
    trace_len = p.max_iters if p.trace_fetches else 0
    hint_len = p.max_iters if p.trace_hints else 0

    entry = jnp.broadcast_to(index.medoid.astype(jnp.int32), (nq,))
    e_d = _adc_batch(index.pq_codes[entry][:, None, :], luts, p.kernels)[:, 0]
    cand_ids = jnp.full((nq, L), -1, jnp.int32).at[:, 0].set(entry)
    cand_d = jnp.full((nq, L), jnp.inf, jnp.float32).at[:, 0].set(e_d)
    if use_hash:
        H = 1 << p.visited_hash_bits
        visited = jnp.full((nq, H), -1, jnp.int32
                           ).at[rows, _hash_slots(entry, p.visited_hash_bits)
                                ].set(entry)
        expanded = jnp.zeros((nq, L), jnp.bool_)    # per candidate slot
    else:
        visited = jnp.zeros((nq, n), jnp.bool_).at[rows, entry].set(True)
        expanded = jnp.zeros((nq, n), jnp.bool_)
    state = (cand_ids, cand_d, visited, expanded,
             jnp.zeros((nq,), jnp.int32),           # iters
             jnp.zeros((nq,), jnp.int32),           # lists fetched
             jnp.zeros((nq,), jnp.int32),           # pq distances (+ entry)
             jnp.zeros((nq,), jnp.int32),           # stability counter
             jnp.full((nq,), -1, jnp.int32),        # prefetch iteration
             jnp.full((nq, KB), -1, jnp.int32),     # prev top-(K+B)
             jnp.full((nq, trace_len, W), -1, jnp.int32),   # fetch trace
             jnp.full((nq, hint_len, W), -1, jnp.int32))    # hint trace

    def _unexpanded(cand_ids, expanded):
        valid = cand_ids >= 0
        if use_hash:
            return valid & ~expanded
        return valid & ~jnp.take_along_axis(
            expanded, jnp.clip(cand_ids, 0, n - 1), 1)

    def _active(cand_ids, expanded, iters):
        return (jnp.any(_unexpanded(cand_ids, expanded), 1)
                & (iters < p.max_iters))

    def has_frontier(st):
        cand_ids, _, _, expanded, iters, *_ = st
        return jnp.any(_active(cand_ids, expanded, iters))

    def step(st):
        (cand_ids, cand_d, visited, expanded, iters, fetched, pq_ct,
         stab, pf_iter, prev_top, trace, hints) = st
        active = _active(cand_ids, expanded, iters)
        unexp = _unexpanded(cand_ids, expanded)
        frontier_d = jnp.where(unexp & active[:, None], cand_d, jnp.inf)
        neg_d, sel_slot = jax.lax.top_k(-frontier_d, W)       # [nq, W]
        sel_ids = jnp.where(jnp.isfinite(neg_d),
                            jnp.take_along_axis(cand_ids, sel_slot, 1), -1)
        if use_hash:
            expanded = expanded.at[rows[:, None], sel_slot].set(
                jnp.take_along_axis(expanded, sel_slot, 1) | (sel_ids >= 0))
        else:
            expanded = expanded.at[
                rows[:, None], jnp.where(sel_ids >= 0, sel_ids, n)].set(
                True, mode="drop")
        fetched = fetched + jnp.sum(sel_ids >= 0, 1).astype(jnp.int32)
        if p.trace_fetches:
            trace = trace.at[rows, iters].set(sel_ids, mode="drop")
        if p.trace_hints:
            # Provisional frontier for round r+1, read BEFORE this round's
            # neighbors merge (its fetches are still in flight): the top-W
            # unexpanded survivors of the current list. Honest speculation —
            # it misses whatever this round discovers closer, which is
            # exactly the engine's live predictor loss.
            prov_d = jnp.where(_unexpanded(cand_ids, expanded)
                               & active[:, None], cand_d, jnp.inf)
            neg_p, prov_slot = jax.lax.top_k(-prov_d, W)
            prov_ids = jnp.where(
                jnp.isfinite(neg_p),
                jnp.take_along_axis(cand_ids, prov_slot, 1), -1)
            hints = hints.at[rows, iters].set(prov_ids, mode="drop")

        nbrs = _gather_neighbors(index, sel_ids, p, n)        # [nq, W*R]
        # Dedupe within the round: single-key sort (fast path on XLA CPU —
        # argsort-with-payload is a scalar loop there) + first-occurrence.
        sorted_n = jnp.sort(nbrs, axis=1)
        first = jnp.concatenate(
            [jnp.ones((nq, 1), jnp.bool_),
             sorted_n[:, 1:] != sorted_n[:, :-1]], 1)
        uniq = jnp.where(first, sorted_n, -1)
        if use_hash:
            H = 1 << p.visited_hash_bits
            slots = _hash_slots(jnp.maximum(uniq, 0), p.visited_hash_bits)
            seen = jnp.take_along_axis(visited, slots, 1) == uniq
            ok = (uniq >= 0) & ~seen
            visited = visited.at[rows[:, None], jnp.where(ok, slots, H)].set(
                jnp.where(ok, uniq, -1), mode="drop")
        else:
            seen = jnp.take_along_axis(visited, jnp.clip(uniq, 0, n - 1), 1)
            ok = (uniq >= 0) & ~seen
            visited = visited.at[rows[:, None], jnp.where(ok, uniq, n)].set(
                True, mode="drop")
        new_ids = jnp.where(ok, uniq, -1)
        codes = index.pq_codes[jnp.clip(new_ids, 0, n - 1)]
        pq_ct = pq_ct + jnp.sum(ok, 1).astype(jnp.int32)

        if p.kernels is not None and p.kernels.beam_step != "off":
            # Fused hop tail (kernels/beam_step): ADC + top-L merge in one
            # launch, per-query LUT resident in VMEM. The ref backend is
            # op-for-op the same jnp as the unfused branch below, so this
            # is a call-structure change, not a semantics change.
            cand_ids, cand_d, top_i = dispatch.beam_step(
                codes, luts, cand_ids, cand_d, new_ids, p.kernels)
        else:
            new_d = jnp.where(ok, _adc_batch(codes, luts, p.kernels),
                              jnp.inf)
            merged_ids = jnp.concatenate([cand_ids, new_ids], 1)
            merged_d = jnp.concatenate([cand_d, new_d], 1)
            top_d, top_i = jax.lax.top_k(-merged_d, L)
            cand_ids = jnp.take_along_axis(merged_ids, top_i, 1)
            cand_d = -top_d
        if use_hash:
            merged_exp = jnp.concatenate(
                [expanded, jnp.zeros_like(new_ids, jnp.bool_)], 1)
            expanded = jnp.take_along_axis(merged_exp, top_i, 1)

        # §3.4 stability: top-(K+B) id set unchanged across expansions.
        top_now = jnp.sort(cand_ids[:, :KB], 1)
        same = jnp.all(top_now == prev_top, 1)
        stab = jnp.where(active, jnp.where(same, stab + W, 0), stab)
        trigger = active & (stab >= p.rerank_batch) & (pf_iter < 0)
        pf_iter = jnp.where(trigger, iters + 1, pf_iter)
        iters = iters + active.astype(jnp.int32)
        prev_top = jnp.where(active[:, None], top_now, prev_top)
        return (cand_ids, cand_d, visited, expanded, iters, fetched, pq_ct,
                stab, pf_iter, prev_top, trace, hints)

    st = jax.lax.while_loop(has_frontier, step, state)
    cand_ids, cand_d = st[0], st[1]
    iters, fetched, pq_ct, _, pf_iter, _, trace, hints = st[4:]
    return cand_ids, cand_d, (iters, fetched, pf_iter, pq_ct + 1, trace,
                              hints)


def rerank(index: DeviceIndex, queries: jnp.ndarray, cand_ids: jnp.ndarray,
           p: SearchParams):
    """Batched phase-2 adaptive re-ranking (§3.4) ->
    (ids [nq, K], dists [nq, K], (batches [nq], exact_ct [nq])).

    All rows consume candidate batch b in lockstep; a row whose benefit
    ratio fired (plus the one-batch lookahead) drops out by masking, so its
    executed-batch count matches a solo run exactly.
    """
    n, K, B = index.vectors.shape[0], p.k, p.rerank_batch
    nq = queries.shape[0]
    if p.filter_tombstones and index.tombstone is None:
        raise ValueError(
            "SearchParams.filter_tombstones=True requires an index with a "
            "tombstone mask (live snapshots set DeviceIndex.tombstone; "
            "frozen indexes leave it None)")
    # Candidates beyond L don't exist; bound the batch loop statically.
    max_batches = min(p.max_rerank_batches, max(0, (p.l_size - K) // B))

    def exact(ids):
        v = index.vectors[jnp.clip(ids, 0, n - 1)]
        d = dispatch.rerank_l2(queries, v, p.kernels)
        if p.filter_tombstones:
            dead = index.tombstone[jnp.clip(ids, 0, n - 1)]
            d = jnp.where(dead, jnp.inf, d)
        return jnp.where(ids >= 0, d, jnp.inf)

    # Batch 0: the prefetched top-K (always re-ranked).
    heap_ids = cand_ids[:, :K]
    heap_d = exact(heap_ids)

    def cond(st):
        _, _, b, go, _, _ = st
        return jnp.any(go) & (b < max_batches)

    def body(st):
        heap_ids, heap_d, b, go, pending_stop, batches = st
        ids = jax.lax.dynamic_slice_in_dim(cand_ids, K + b * B, B, axis=1)
        d = jnp.where(go[:, None], exact(ids), jnp.inf)
        m_ids = jnp.concatenate([heap_ids, ids], 1)
        m_d = jnp.concatenate([heap_d, d], 1)
        top_d, top_i = jax.lax.top_k(-m_d, K)
        new_ids = jnp.take_along_axis(m_ids, top_i, 1)
        new_d = -top_d
        displaced = jnp.sum(top_i >= K, 1).astype(jnp.float32)
        below = displaced / B < p.benefit_threshold
        heap_ids = jnp.where(go[:, None], new_ids, heap_ids)
        heap_d = jnp.where(go[:, None], new_d, heap_d)
        batches = batches + go.astype(jnp.int32)
        # one-batch lookahead (§3.4): the next batch is already in flight
        # when the benefit test fires, so termination lags one batch.
        go_next = go & (~pending_stop | ~below)
        pending_stop = jnp.where(go, below, pending_stop)
        return (heap_ids, heap_d, b + 1, go_next, pending_stop, batches)

    heap_ids, heap_d, _, _, _, batches = jax.lax.while_loop(
        cond, body, (heap_ids, heap_d, jnp.int32(0),
                     jnp.ones((nq,), jnp.bool_), jnp.zeros((nq,), jnp.bool_),
                     jnp.zeros((nq,), jnp.int32)))
    order = jnp.argsort(heap_d, axis=1)
    ids = jnp.take_along_axis(heap_ids, order, 1)
    dists = jnp.take_along_axis(heap_d, order, 1)
    if p.filter_tombstones:
        # A tombstoned (masked-to-inf) id must never surface: -1 = no result.
        ids = jnp.where(jnp.isfinite(dists), ids, -1)
    exact_ct = (K + batches * B).astype(jnp.int32)
    return ids, dists, (batches, exact_ct)


def search_batched(index: DeviceIndex, queries: jnp.ndarray, p: SearchParams):
    """Batch-first search core (unjitted — compose inside jit/shard_map).

    queries [nq, d] -> (ids [nq, K], dists [nq, K], SearchStats of [nq]).

    ``p.kernels`` should already be resolved (``resolve_kernels``) by the
    caller that builds the program; the fallback here only fires for ad-hoc
    direct calls with a None/auto config. A concrete config passes through
    UNTOUCHED — re-resolving here would re-query the platform inside the
    caller's trace and silently rewrite a deliberately pinned ``pallas``
    config when the driving process's default backend differs from the
    target mesh.
    """
    if p.kernels is None or not p.kernels.is_resolved:
        p = resolve_kernels(p)
    # Named scopes are HLO metadata only: they tag each device op with its
    # phase (the benchmark's trace reduction sums device time by them) and
    # leave the compiled program as it was.
    with jax.named_scope("beam.lut"):
        luts = jax.vmap(
            lambda q: build_lut_jnp(q.astype(jnp.float32), index.pq_centroids)
        )(queries)
    with jax.named_scope("beam.traverse"):
        cand_ids, cand_d, (iters, fetched, pf_iter, pq_ct, trace, hints) = \
            traverse(index, luts, p)
    with jax.named_scope("beam.rerank"):
        ids, dists, (batches, exact_ct) = rerank(index, queries, cand_ids, p)
    stats = SearchStats(iters, fetched, pf_iter, batches, exact_ct,
                        pq_ct, trace, hints)
    return ids, dists, stats


@functools.partial(jax.jit, static_argnames=("p",))
def _search_jit(index: DeviceIndex, queries: jnp.ndarray, p: SearchParams):
    return search_batched(index, queries, p)


def search(index: DeviceIndex, queries: jnp.ndarray, p: SearchParams):
    """Batched search -> (ids [nq, K], dists [nq, K], stats of [nq] each).

    Resolves ``p.kernels`` before entering jit (config time), so each
    backend choice is a distinct static compilation, never a traced check.
    """
    return _search_jit(index, queries, resolve_kernels(p))


def search_one(index: DeviceIndex, query: jnp.ndarray, p: SearchParams):
    """Single-query search: the nq=1 case of the batch-first path."""
    ids, dists, stats = search(index, query[None], p)
    return ids[0], dists[0], jax.tree_util.tree_map(lambda x: x[0], stats)


@functools.partial(jax.jit, static_argnames=("p",))
def _candidates_jit(index: DeviceIndex, queries: jnp.ndarray, p: SearchParams):
    luts = jax.vmap(
        lambda q: build_lut_jnp(q.astype(jnp.float32), index.pq_centroids)
    )(queries)
    cand_ids, cand_d, _ = traverse(index, luts, p)
    return cand_ids, cand_d


def search_candidates(index: DeviceIndex, queries: jnp.ndarray,
                      p: SearchParams):
    """Batched traversal WITHOUT the re-rank phase ->
    (cand_ids [nq, L], pq_dists [nq, L]), -1 = empty slot.

    This is the §3.5 insert path's candidate pool: a fresh point's robust-
    prune input is the candidate list its own search would produce, so the
    streaming-update tier runs the exact same beam core as serving — one
    batched call for the whole insert buffer instead of a Python greedy
    loop per point. Distances are PQ (ADC) approximations; insert-side
    pruning re-ranks with exact vectors on the host."""
    return _candidates_jit(index, queries, resolve_kernels(p))


@functools.partial(jax.jit, static_argnames=("p",))
def _search_vmapped_jit(index: DeviceIndex, queries: jnp.ndarray,
                        p: SearchParams):
    def solo(q):
        ids, dists, stats = search_batched(index, q[None], p)
        return (ids[0], dists[0],
                jax.tree_util.tree_map(lambda x: x[0], stats))
    return jax.vmap(solo)(queries)


def search_vmapped(index: DeviceIndex, queries: jnp.ndarray, p: SearchParams):
    """Legacy per-query vmap formulation (the pre-batching baseline).

    vmap of a while_loop selects EVERY carry each round for every lane, so
    this pays O(nq * n) visited/select traffic per round; kept for the
    batched-vs-vmapped comparison in bench_serve_ann (~3x on XLA CPU,
    growing with n).
    """
    return _search_vmapped_jit(index, queries, resolve_kernels(p))
