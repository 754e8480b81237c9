"""Kernel tier benchmark: ref vs pallas per op × size, the fused beam-step
kernel vs its unfused composition, and the end-to-end batched search under
each backend — written to ``BENCH_kernels.json``. Every (op, backend,
shape) measurement is also recorded into the persisted autotune cache
(``repro.kernels.autotune``), which is what ``REPRO_KERNELS=auto-tuned``
resolves from: this bench IS the autotuner.

Backends go through the dispatch layer exactly as the hot path does: the
``pallas`` request resolves at config time (compiled Mosaic kernel on TPU;
the interpreter on this CPU container). Interpret-mode timings are
CORRECTNESS-mode numbers — they validate that the kernel programs run and
agree, they do not measure kernel performance; on CPU the deployable path
is ``ref`` (the jnp oracle XLA compiles). The JSON records which mode the
pallas column ran in so downstream comparisons stay honest. The autotune
cache is keyed by platform for the same reason: CPU (interpreter)
measurements never drive TPU decisions.

The ``auto_tuned`` section is the dispatch-rule gate: for every measured
(op, shape) the cache's pick must match the measured argmin, i.e.
``auto-tuned`` can NEVER resolve to a backend that lost its own bench
(``never_loses`` is asserted here and checked again in CI).

Env: REPRO_BENCH_KERNELS_N rescales the e2e corpus (default 768);
REPRO_BENCH_ITERS rescales per-op timing iterations (default 20; CI smoke
uses 3); REPRO_BENCH_OUT overrides the JSON path (default
./BENCH_kernels.json); REPRO_AUTOTUNE_CACHE overrides where the cache is
written (default src/repro/kernels/autotune_cache.json, the committed one).
"""
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.core.codec.elias_fano import encode_slot
from repro.core.index import build_device_index, recall_at_k
from repro.core.search.beam import SearchParams, search
from repro.data.synthetic import ground_truth, make_queries, make_vector_dataset
from repro.kernels import dispatch
from repro.kernels.autotune import AutotuneCache
from repro.kernels.dispatch import KernelConfig

from .common import csv

REF = KernelConfig("ref", "ref", "ref", "ref", "off")
ITERS = int(os.environ.get("REPRO_BENCH_ITERS", 20))


def _bench(fn, *args, iters=ITERS):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def _beam_step_unfused(codes, luts, cand_ids, cand_d, new_ids, cfg):
    """The pre-fusion hot-sequence: separate dispatch calls per op, merge in
    XLA — what the beam loop runs when ``beam_step == "off"``."""
    l_size = cand_ids.shape[1]
    d = dispatch.pq_adc_batched(codes, luts, cfg)
    new_d = jnp.where(new_ids >= 0, d, jnp.inf)
    merged_ids = jnp.concatenate([cand_ids, new_ids], 1)
    merged_d = jnp.concatenate([cand_d, new_d], 1)
    top_d, top_i = jax.lax.top_k(-merged_d, l_size)
    return jnp.take_along_axis(merged_ids, top_i, 1), -top_d


def _op_rows(pallas_cfg, cache):
    rng = np.random.default_rng(0)
    rows = []
    measured = {}   # (op, size) -> {resolved backend name: us}

    def add(op, size, call, dims, iters=ITERS, arms=None):
        arms = arms or (("ref", "ref", REF), ("pallas", pallas_cfg.pq_adc,
                                              pallas_cfg))
        for label, resolved, cfg in arms:
            us = _bench(lambda: call(cfg), iters=iters)
            rows.append(dict(op=op, backend=label, resolved=resolved,
                             size=size, us=round(us, 2)))
            measured.setdefault((op, size), {})[resolved] = us
            cache.record(op, resolved, us, **dims)
            csv(f"kernel/{op}/{label}", us, size)

    for n in (1024, 4096):
        codes = jnp.asarray(rng.integers(0, 256, (n, 8), dtype=np.uint8))
        lut = jnp.asarray(rng.normal(size=(8, 256)).astype(np.float32))
        add("pq_adc", f"n={n};m=8;k=256",
            lambda cfg, c=codes, l=lut: dispatch.pq_adc(c, l, cfg),
            dict(n=n, m=8, k=256))

    codes_b = jnp.asarray(rng.integers(0, 256, (32, 128, 8), dtype=np.uint8))
    luts_b = jnp.asarray(rng.normal(size=(32, 8, 256)).astype(np.float32))
    add("pq_adc_batched", "nq=32;n=128;m=8",
        lambda cfg: dispatch.pq_adc_batched(codes_b, luts_b, cfg),
        dict(nq=32, n=128, m=8))

    slots = jnp.asarray(np.stack([
        encode_slot(np.sort(rng.choice(10**6, 24, replace=False)
                            .astype(np.uint64)), 32, 10**6)
        for _ in range(256)]))
    add("ef_decode", "lists=256;r=32;u=1e6",
        lambda cfg: dispatch.ef_decode(slots, 32, 10**6, cfg),
        dict(lists=256, r=32), iters=min(ITERS, 5))

    # q=32;c=130;d=64 is the non-tile-aligned regression shape: the fixed
    # (8, 128) tiling paid 8 padded grid steps here (1748 µs vs 308 ref,
    # pre-roofline BENCH_kernels.json); the roofline planner covers it in 1.
    for q, c, d in ((8, 128, 128), (32, 130, 64)):
        qs = jnp.asarray(rng.normal(size=(q, d)).astype(np.float32))
        cs = jnp.asarray(rng.normal(size=(q, c, d)).astype(np.float32))
        add("rerank_l2", f"q={q};c={c};d={d}",
            lambda cfg, a=qs, b=cs: dispatch.rerank_l2(a, b, cfg),
            dict(q=q, c=c, d=d))

    packed = jnp.asarray(rng.integers(0, 256, (4096, 128), dtype=np.uint8))
    base = jnp.asarray(rng.integers(0, 256, 128, dtype=np.uint8))
    add("byteplane", "n=4096;v=128",
        lambda cfg: dispatch.byteplane_decode(packed, base, cfg),
        dict(n=4096, v=128))

    # Fused beam step vs the unfused composition it replaces. ``off`` is a
    # real contender in the cache: the autotuner arbitrates fusion itself.
    nq, e, l_size, m = 32, 64, 48, 8
    codes_f = jnp.asarray(rng.integers(0, 256, (nq, e, m), dtype=np.uint8))
    luts_f = jnp.asarray(rng.normal(size=(nq, m, 256)).astype(np.float32))
    cand_d = jnp.sort(jnp.asarray(
        rng.normal(size=(nq, l_size)).astype(np.float32) ** 2), axis=1)
    cand_ids = jnp.asarray(
        rng.integers(0, 10**6, (nq, l_size)).astype(np.int32))
    new_ids = jnp.where(jnp.asarray(rng.random((nq, e))) < 0.9,
                        jnp.asarray(rng.integers(0, 10**6, (nq, e))), -1
                        ).astype(jnp.int32)
    size = f"nq={nq};e={e};l={l_size};m={m}"
    dims = dict(nq=nq, e=e, l=l_size, m=m)
    add("beam_step", size,
        lambda cfg: dispatch.beam_step(codes_f, luts_f, cand_ids, cand_d,
                                       new_ids, cfg),
        dims,
        arms=(("ref", "ref", REF._replace(beam_step="ref")),
              ("pallas", pallas_cfg.pq_adc,
               pallas_cfg._replace(beam_step=pallas_cfg.pq_adc))))
    add("beam_step", size,
        lambda cfg: _beam_step_unfused(codes_f, luts_f, cand_ids, cand_d,
                                       new_ids, cfg),
        dims, arms=(("off", "off", REF),))
    return rows, measured


def _auto_tuned_rows(measured, cache, dims_by_key):
    """Resolve each measured (op, shape) through the cache and GATE: the
    pick must be the measured argmin — auto-tuned never loses a bench."""
    rows, never_loses = [], True
    for (op, size), by_backend in sorted(measured.items()):
        pick = cache.best(op, dims_by_key[op, size], fallback="ref")
        best_us = min(by_backend.values())
        us = by_backend.get(pick)
        ok = us is not None and us <= best_us + 1e-9
        never_loses &= ok
        rows.append(dict(op=op, backend="auto-tuned", resolved=pick,
                         size=size, us=round(us, 2) if us else None,
                         never_loses=bool(ok)))
        csv(f"kernel/{op}/auto-tuned", us or -1.0, f"{size};pick={pick}")
    assert never_loses, f"auto-tuned resolved to a bench-losing backend: " \
        f"{[r for r in rows if not r['never_loses']]}"
    return rows, never_loses


def _e2e_rows(pallas_cfg, auto_cfg, n, nq=32, reps=3):
    dim, r, pq_m = 32, 16, 4
    vecs = make_vector_dataset("sift-like", n, dim, seed=0).astype(np.float32)
    queries = make_queries("sift-like", nq, dim).astype(np.float32)
    gt = ground_truth(vecs, queries, k=10)
    index, _, _ = build_device_index(vecs, r=r, l_build=32, pq_m=pq_m, seed=0)
    base = SearchParams(l_size=48, beam_width=4, k=10, rerank_batch=10,
                        r_max=r, universe=n, max_iters=128)
    arms = (("ref", REF),                                   # unfused jnp
            ("fused", REF._replace(beam_step="ref")),       # fused call, jnp
            ("pallas", pallas_cfg._replace(
                beam_step=pallas_cfg.pq_adc)),              # fused kernel
            ("auto-tuned", auto_cfg))
    rows, ids_by_arm = [], {}
    for name, cfg in arms:
        p = base._replace(kernels=cfg)
        qj = jnp.asarray(queries)
        ids, _, _ = search(index, qj, p)              # compile + warm
        jax.block_until_ready(ids)
        t0 = time.perf_counter()
        for _ in range(reps):
            ids, dists, _ = search(index, qj, p)
        jax.block_until_ready(ids)
        us_q = (time.perf_counter() - t0) * 1e6 / (reps * nq)
        rec = recall_at_k(np.asarray(ids), gt, 10)
        ids_by_arm[name] = np.asarray(ids)
        rows.append(dict(op="search_batched", backend=name,
                         kernels=dict(cfg._asdict()),
                         size=f"n={n};nq={nq};dim={dim}",
                         us_per_query=round(us_q, 2),
                         qps=round(1e6 / us_q), recall_at_10=round(rec, 4)))
        csv(f"kernel/search_batched/{name}", us_q,
            f"n={n};nq={nq};qps={1e6/us_q:.0f};recall={rec:.3f}")
    # Fusion is an execution-plan change, not an algorithm change: the fused
    # arms must return bit-identical ids to the unfused ref arm.
    for arm in ("fused", "pallas"):
        assert (ids_by_arm[arm] == ids_by_arm["ref"]).all(), \
            f"fused arm {arm!r} diverged from ref ids"
    return rows


def main(quiet=False):
    platform = jax.default_backend()
    pallas_cfg = KernelConfig("pallas", "pallas", "pallas", "pallas",
                              "off").resolve()
    n = int(os.environ.get("REPRO_BENCH_KERNELS_N", 768))
    cache = AutotuneCache(platform=platform)
    ops, measured = _op_rows(pallas_cfg, cache)
    cache_path = cache.save()
    dims_by_key = {}
    for row in ops:
        dims_by_key.setdefault((row["op"], row["size"]), dict(
            kv.split("=") for kv in row["size"].split(";")))
    # re-parse dims as ints where possible (size strings like u=1e6 stay out)
    dims_by_key = {k: {kk: int(v) for kk, v in d.items() if v.isdigit()}
                   for k, d in dims_by_key.items()}
    auto_rows, never_loses = _auto_tuned_rows(measured, cache, dims_by_key)
    auto_cfg = KernelConfig(*(["auto-tuned"] * 5)).resolve(platform)
    e2e = _e2e_rows(pallas_cfg, auto_cfg, n)
    doc = dict(
        platform=platform,
        pallas_resolved_as=pallas_cfg.pq_adc,
        note=("pallas timings are interpreter (correctness) mode off-TPU — "
              "compare ref vs pallas only where pallas_resolved_as=='pallas'"),
        autotune_cache=str(cache_path),
        auto_tuned=dict(never_loses=bool(never_loses),
                        resolved_config=dict(auto_cfg._asdict()),
                        rows=auto_rows),
        ops=ops, e2e=e2e)
    out = os.environ.get("REPRO_BENCH_OUT", "BENCH_kernels.json")
    with open(out, "w") as f:
        json.dump(doc, f, indent=2)
    if not quiet:
        print(f"# wrote {out} ({len(ops)} op rows, {len(e2e)} e2e rows, "
              f"auto-tuned never_loses={never_loses})")


if __name__ == "__main__":
    enable_compile_cache()
    main()
