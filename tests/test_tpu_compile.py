"""Compile the search path for a TPU v5e that is described, not attached.

Interpret-mode tests cannot see what the TPU's compiler refuses: block
shapes off the (8, 128) tiling, ops Mosaic cannot lower, programs that do
not fit HBM. Each test here lowers with ``interpret=False`` against a
described ``v5e:2x2`` topology and compiles, so such a refusal fails here
instead of on the chip. Nothing runs; results are checked elsewhere.

Widths are ``chip_smoke.py``'s: the serve phase (R=32, n=2048) and the
capacity phase (the production config's R=128, PQ m=32, d=128 at n=2^20),
32-query buckets, beam width 4, L=200.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import
this file.
"""
import functools

import pytest

import jax
import jax.numpy as jnp

NQ, W, L, K, D, M, KC = 32, 4, 200, 10, 128, 32, 256
WIDTHS = {"serve": (32, 2048), "capacity": (128, 1 << 20)}   # (R, n)
HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo, SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _kernel_case(name: str, r: int, n: int, spec):
    """(fn, arg shapes) of one kernel at the given widths."""
    from repro.core.codec.elias_fano import slot_layout
    from repro.kernels.beam_step.beam_step import beam_step_pallas
    from repro.kernels.byteplane.byteplane import byteplane_decode_pallas
    from repro.kernels.ef_decode.ef_decode import ef_decode_pallas
    from repro.kernels.pq_adc.pq_adc import (pq_adc_batched_pallas,
                                            pq_adc_pallas)
    from repro.kernels.rerank_l2.rerank_l2 import rerank_l2_pallas
    s = spec
    e = W * r
    cases = {
        "pq_adc": (pq_adc_pallas, s((min(n, 4096), M), jnp.uint8),
                   s((M, KC))),
        "pq_adc_batched": (pq_adc_batched_pallas, s((NQ, e, M), jnp.uint8),
                           s((NQ, M, KC))),
        "ef_decode": (functools.partial(ef_decode_pallas, r_max=r,
                                        universe=n),
                      s((NQ * W, slot_layout(r, n)[3]), jnp.uint32)),
        "rerank_l2": (rerank_l2_pallas, s((NQ, D)), s((NQ, K, D))),
        "byteplane": (byteplane_decode_pallas, s((4096, D), jnp.uint8),
                      s((D,), jnp.uint8)),
        "beam_step": (beam_step_pallas, s((NQ, e, M), jnp.uint8),
                      s((NQ, M, KC)), s((NQ, L), jnp.int32), s((NQ, L)),
                      s((NQ, e), jnp.int32)),
    }
    fn, *shapes = cases[name]
    return functools.partial(fn, interpret=False), shapes


def _on(sharding):
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


@pytest.mark.parametrize("width", sorted(WIDTHS))
@pytest.mark.parametrize("kernel", ["pq_adc", "pq_adc_batched", "ef_decode",
                                    "rerank_l2", "byteplane", "beam_step"])
def test_kernel_compiles_for_v5e(one_chip, kernel, width):
    _, chip = one_chip
    fn, shapes = _kernel_case(kernel, *WIDTHS[width], _on(chip))
    compiled = _compile(fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()


def test_capacity_search_program_fits_hbm(one_chip):
    """The served search program at the capacity phase's shapes, every op
    on compiled Pallas, with its 2^20-row state, fits one chip's HBM."""
    from repro.core.codec.elias_fano import slot_layout
    from repro.core.search.beam import DeviceIndex, SearchParams, \
        search_batched
    from repro.kernels.dispatch import KernelConfig
    _, chip = one_chip
    s = _on(chip)
    r, n = WIDTHS["capacity"]
    index = DeviceIndex(
        neighbors=s((n, r), jnp.int32), counts=s((n,), jnp.int32),
        ef_slots=s((n, slot_layout(r, n)[3]), jnp.uint32),
        pq_codes=s((n, M), jnp.uint8), pq_centroids=s((M, KC, D // M)),
        vectors=s((n, D)), medoid=s((), jnp.int32))
    pallas = KernelConfig(*(["pallas"] * len(KernelConfig._fields)))
    p = SearchParams(l_size=L, beam_width=W, k=K, rerank_batch=10, r_max=r,
                     universe=n, visited_hash_bits=15, trace_fetches=True,
                     kernels=pallas)
    compiled = _compile(lambda idx, q: search_batched(idx, q, p)[:2],
                        index, s((NQ, D)))
    assert compiled.as_text().count("tpu_custom_call") >= 4
    mem = compiled.memory_analysis()
    # The traversal reads the EF slots, the re-rank reads the vectors.
    state = n * (slot_layout(r, n)[3] * 4 + D * 4)
    assert mem.argument_size_in_bytes >= state
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used


def test_sharded_search_compiles_for_four_chips(one_chip):
    """chip_smoke.py --chips 4's program: a 4-shard index, one shard per
    chip of the 2x2 mesh, searched by shard_map with the hierarchical
    merge."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.codec.elias_fano import slot_layout
    from repro.core.distributed.sharded_index import (ShardedIndex,
                                                      make_sharded_search)
    from repro.core.search.beam import SearchParams
    topo, _ = one_chip
    mesh = jax.make_mesh((4,), ("data",), devices=topo.devices[:4])
    r, n = WIDTHS["serve"]
    per = n // 4
    s, q = _on(NamedSharding(mesh, P("data"))), _on(NamedSharding(mesh, P()))
    index = ShardedIndex(
        neighbors=s((4, per, r), jnp.int32), counts=s((4, per), jnp.int32),
        ef_slots=s((4, per, slot_layout(r, per)[3]), jnp.uint32),
        pq_codes=s((4, per, M), jnp.uint8),
        pq_centroids=s((4, M, KC, D // M)), vectors=s((4, per, D)),
        medoid=s((4,), jnp.int32), row_ids=s((4, per), jnp.int32))
    p = SearchParams(l_size=L, beam_width=W, k=K, rerank_batch=10, r_max=r,
                     universe=per)
    run = make_sharded_search(mesh, p, merge="hier")
    compiled = run.lower(index, q((NQ, D))).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "collective-permute" in text


@pytest.mark.parametrize("bucket", [1, 8, 32])
def test_served_sharded_bucket_compiles_for_four_chips(one_chip, bucket):
    """The bucket program ``BatchedSearcher`` runs over a placed 4-shard
    index at the ``bigann-share-4x`` cell's widths: R=128, 2^18 rows a
    shard, EF slots at the universe 2^20, PQ m=32, L=200, W=4, a dense
    visited set, the fetch trace and every shard's stats out, the merge on
    the chips; its per-chip state fits one chip's HBM."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.codec.elias_fano import slot_layout
    from repro.core.distributed.sharded_index import (ShardedIndex,
                                                      make_masked_search)
    from repro.core.search.beam import SearchParams
    from repro.kernels.dispatch import KernelConfig
    topo, _ = one_chip
    mesh = jax.make_mesh((4,), ("data",), devices=topo.devices[:4])
    r, per, universe = 128, 1 << 18, 1 << 20
    s, q = _on(NamedSharding(mesh, P("data"))), _on(NamedSharding(mesh, P()))
    index = ShardedIndex(
        neighbors=s((4, per, r), jnp.int32), counts=s((4, per), jnp.int32),
        ef_slots=s((4, per, slot_layout(r, universe)[3]), jnp.uint32),
        pq_codes=s((4, per, M), jnp.uint8),
        pq_centroids=s((4, M, KC, D // M)), vectors=s((4, per, D)),
        medoid=s((4,), jnp.int32), row_ids=s((4, per), jnp.int32))
    pallas = KernelConfig(*(["pallas"] * len(KernelConfig._fields)))
    p = SearchParams(l_size=L, beam_width=W, k=K, rerank_batch=10, r_max=r,
                     universe=universe, trace_fetches=True, kernels=pallas)
    stats = ("exact_dists", "fetch_trace", "iters", "pq_dists",
             "rerank_batches")
    run = make_masked_search(mesh, p, stats=stats)
    compiled = run.lower(index, q((bucket, D)),
                         q((bucket, 4), jnp.bool_)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "collective-permute" in text
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used
