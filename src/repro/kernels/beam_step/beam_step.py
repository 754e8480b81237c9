"""Fused beam-step kernel: ADC LUT lookup + candidate top-L merge in VMEM.

The unfused traversal loop launches three device programs per beam hop
(batched ADC, neighbor gather glue, top-L merge) and round-trips every
intermediate — the [nq, E] distance block, the [nq, L+E] merged lists —
through HBM between them. This kernel fuses the hop's compute tail into ONE
``pallas_call``: per grid step a single query's LUT, its gathered codes,
and its candidate list are loaded to VMEM once, the ADC lookup runs as the
exact select-and-sum of ``pq_adc.adc_rows``, and the merged top-L is
selected in VMEM before only the [L] results are written back. Per-query
LUT tiling is the grid itself: step ``i`` holds query ``i``'s LUT resident.

Top-L selection is a *stable rank* select, not a sort: with T candidates,
``rank[i] = #{j : d[j] < d[i] or (d[j] == d[i] and key[j] < key[i])}`` is a
[T, T] compare + row-sum (VPU work), and output slot p takes the element
with rank p via a one-hot [T, L] mask. ``key`` is the element's index in
the unpadded concatenation [cand | new], so this reproduces
``jax.lax.top_k`` tie-breaking exactly (equal distances -> lower merged
index first), which is what makes the fused path bit-identical to the
unfused ref program — the conformance gate in
tests/test_kernel_conformance.py.

Layout: every per-query operand is a lane-dense row. The candidate list is
padded to Lp and the neighbor axis to Ep (multiples of 128), and the arrays
are shaped [nq, 1, X] so that each block's last two dims are a whole
(1, X) row, as the TPU's (8, 128) tiling rule requires. Pad slots carry
(+inf, -1) and keys above every real key, so they rank after every real
entry; there are always >= L real entries (the candidate list itself), so
no pad slot is ever selected. The rank needs each distance both as a row
and as a column; the column comes from one in-VMEM transpose.

Per-step VMEM (f32 words unless noted): the [K, Ep] select of the ADC, the
[T, T] compare (T = Lp + Ep; 2.3 MiB at T = 768) and the [T, Lp] select.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..pq_adc.pq_adc import adc_rows

LANE = 128   # list and neighbor axes are padded to the lane width


def _column(row: jnp.ndarray) -> jnp.ndarray:
    """[1, T] -> [T, 1] (a transpose of 8 broadcast rows: the TPU
    transposes whole (8, 128) tiles)."""
    return jnp.broadcast_to(row, (8, row.shape[1])).T[:, 0:1]


def _merge_keys(shape, axis, l_size, e_size, lp):
    """Tie-break key of each merged slot: its index in the UNPADDED
    [cand(L) | new(E)] concatenation; pad slots get keys after all real
    ones (cand pads at L+E.., new pads at Lp+E..), keeping keys a
    permutation of [0, T)."""
    pos = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
    return jnp.where(pos < l_size, pos,
                     jnp.where(pos < lp, pos + e_size,
                               jnp.where(pos < lp + e_size,
                                         pos - lp + l_size, pos)))


def _kernel(codes_ref, lut_ref, ci_ref, cd_ref, ni_ref,
            oi_ref, od_ref, ox_ref, *, l_size: int, e_size: int):
    lp = cd_ref.shape[2]
    ni = ni_ref[0]                                        # [1, Ep]
    t = lp + ni.shape[1]
    # ---- ADC: exact lookup, same sum order as the oracle
    d_new = adc_rows(codes_ref[0].astype(jnp.int32), lut_ref[0])
    d_new = jnp.where(ni >= 0, d_new, jnp.inf)            # [1, Ep]
    # ---- merge: stable-rank top-L over [cand | new], all in VMEM
    md = jnp.concatenate([cd_ref[0], d_new], axis=1)      # [1, T]
    mi = jnp.concatenate([ci_ref[0], ni], axis=1)         # [1, T]
    key_row = _merge_keys((1, t), 1, l_size, e_size, lp)
    key_col = _merge_keys((t, 1), 0, l_size, e_size, lp)
    md_col, mi_col = _column(md), _column(mi)
    before = (md < md_col) | ((md == md_col) & (key_row < key_col))
    rank = jnp.sum(before.astype(jnp.int32), axis=1, keepdims=True)  # [T, 1]
    hit = rank == jax.lax.broadcasted_iota(jnp.int32, (1, lp), 1)    # [T, Lp]
    od_ref[0] = jnp.sum(jnp.where(hit, md_col, 0.0), axis=0, keepdims=True)
    oi_ref[0] = jnp.sum(jnp.where(hit, mi_col, 0), axis=0, keepdims=True)
    ox_ref[0] = jnp.sum(jnp.where(hit, key_col, 0), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def beam_step_pallas(codes: jnp.ndarray, luts: jnp.ndarray,
                     cand_ids: jnp.ndarray, cand_d: jnp.ndarray,
                     new_ids: jnp.ndarray, interpret: bool = True):
    """Fused hop tail: see ``ref.beam_step_ref`` for the contract.

    Grid is (nq,): one query per step, its LUT + candidate state resident.
    ``top_idx`` always indexes the UNPADDED concatenation, exactly like
    the oracle.
    """
    nq, e, m = codes.shape
    nq2, m2, k = luts.shape
    nq3, l_size = cand_ids.shape
    assert nq == nq2 == nq3 and m == m2
    assert new_ids.shape == (nq, e) and cand_d.shape == (nq, l_size)
    ep, lp = e + (-e) % LANE, l_size + (-l_size) % LANE
    codes_t = jnp.pad(codes, ((0, 0), (0, ep - e), (0, 0))).transpose(0, 2, 1)
    row = lambda x, width, fill: jnp.pad(
        x, ((0, 0), (0, width - x.shape[1])), constant_values=fill)[:, None]
    kernel = functools.partial(_kernel, l_size=l_size, e_size=e)
    per_query = lambda *block: pl.BlockSpec(block, lambda i: (i, 0, 0))
    out_ids, out_d, out_idx = pl.pallas_call(
        kernel,
        grid=(nq,),
        in_specs=[per_query(1, m, ep), per_query(1, k, m),
                  per_query(1, 1, lp), per_query(1, 1, lp),
                  per_query(1, 1, ep)],
        out_specs=[per_query(1, 1, lp)] * 3,
        out_shape=[jax.ShapeDtypeStruct((nq, 1, lp), jnp.int32),
                   jax.ShapeDtypeStruct((nq, 1, lp), jnp.float32),
                   jax.ShapeDtypeStruct((nq, 1, lp), jnp.int32)],
        interpret=interpret,
    )(codes_t, luts.astype(jnp.float32).transpose(0, 2, 1),
      row(cand_ids.astype(jnp.int32), lp, -1),
      row(cand_d.astype(jnp.float32), lp, jnp.inf),
      row(new_ids.astype(jnp.int32), ep, -1))
    return (out_ids[:, 0, :l_size], out_d[:, 0, :l_size],
            out_idx[:, 0, :l_size])
