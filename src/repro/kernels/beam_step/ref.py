"""Oracle for the fused beam step (ADC + candidate-list top-L merge).

This is LITERALLY the unfused hot-sequence from ``core/search/beam.py``'s
traversal loop — the same jnp ops in the same order — so routing the loop
through ``beam_step`` with the ``ref`` backend is bit-identical to the
pre-fusion program: same distances, same ``lax.top_k`` tie-breaking (equal
distances resolve to the lower merged index), same ids. The fused pallas
kernel is validated against THIS function.
"""
import jax
import jax.numpy as jnp

from ..pq_adc.ref import pq_adc_batched_ref


def beam_step_ref(codes: jnp.ndarray, luts: jnp.ndarray,
                  cand_ids: jnp.ndarray, cand_d: jnp.ndarray,
                  new_ids: jnp.ndarray):
    """One beam hop's compute tail, batched over queries.

    codes    [nq, E, M] uint8   PQ codes gathered for this hop's E neighbors
    luts     [nq, M, K] f32     per-query ADC lookup tables
    cand_ids [nq, L]    i32     current candidate list (-1 = empty slot)
    cand_d   [nq, L]    f32     current candidate PQ distances (+inf = empty)
    new_ids  [nq, E]    i32     deduped neighbor ids not in the list
                                (-1 = masked)

    Returns ``(cand_ids' [nq, L], cand_d' [nq, L], top_idx [nq, L])`` — the
    merged top-L by (distance, merged index) where merged = [cand | new];
    ``top_idx`` indexes that concatenation (callers use it to permute
    side-car state such as the per-slot ``expanded`` flags).
    """
    l_size = cand_ids.shape[1]
    d = pq_adc_batched_ref(codes, luts)
    new_d = jnp.where(new_ids >= 0, d, jnp.inf)
    merged_ids = jnp.concatenate([cand_ids, new_ids], 1)
    merged_d = jnp.concatenate([cand_d, new_d], 1)
    top_d, top_i = jax.lax.top_k(-merged_d, l_size)
    return (jnp.take_along_axis(merged_ids, top_i, 1), -top_d,
            top_i.astype(jnp.int32))
