"""PQ ADC as an exact in-VMEM table lookup — the TPU formulation.

On CPU (the paper's target) ADC is a per-byte table gather; a TPU has no
fast vector gather, so the lookup ``lut[m, code]`` becomes a compare-select
over the K table entries followed by a sublane sum. Exactly one entry
survives the select, so the sum is the table value itself, bit for bit.
The M sub-distances are then added left to right, the same order
``ref.pq_adc_ref`` adds them, so both backends return identical floats and
the beam search takes identical paths under either.

Layout: codes are transposed to [M, n] so that one subspace's codes form a
lane-dense row, and the LUT to [K, M] so that one subspace's table is a
column; the [K, n] select then needs no relayout, and each grid step writes
a lane-dense [1, BN] row of distances.

Tiling: grid over row-blocks of BN codes. Per step the kernel holds in VMEM:
  codes block [M, BN]  (uint8 or int32)
  lut         [K, M]   f32          (K=256, M<=64 -> <=64 KiB)
  select      [K, BN]  f32/int32    (the dominant term, a few live copies)
  out block   [1, BN]  f32

BN is CHOSEN PER SHAPE by the roofline tile planner (launch/roofline.py):
fewest grid steps subject to the select tiles fitting VMEM_TILE_BUDGET.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.launch import roofline

BN = 128  # row-block floor (the planner's smallest candidate)


@functools.lru_cache(maxsize=None)
def _plan_bn(n: int, m: int, k: int) -> int:
    """Rows per grid step for an [n, M] x [M, K] ADC. Static per shape."""
    return roofline.choose_tile(
        n, (BN, 256, 512, 1024),
        lambda bn: (bn * m + m * k + 3 * k * bn + bn) * 4)


def adc_rows(codes_t: jnp.ndarray, lut_t: jnp.ndarray) -> jnp.ndarray:
    """In-kernel ADC: codes_t [M, n] int32, lut_t [K, M] f32 -> [1, n].

    ``sum_m lut[m, codes[m, i]]`` with the sum taken left to right over m
    (the oracle's order); each lookup is an exact select-and-sum."""
    m, n = codes_t.shape
    kk = jax.lax.broadcasted_iota(jnp.int32, (lut_t.shape[0], n), 0)
    acc = None
    for j in range(m):
        hit = kk == codes_t[j:j + 1, :]                       # [K, n]
        v = jnp.sum(jnp.where(hit, lut_t[:, j:j + 1], 0.0), axis=0,
                    keepdims=True)                            # [1, n]
        acc = v if acc is None else acc + v
    return acc


def _kernel(codes_ref, lut_ref, out_ref):
    out_ref[...] = adc_rows(codes_ref[...].astype(jnp.int32), lut_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def pq_adc_pallas(codes: jnp.ndarray, lut: jnp.ndarray,
                  interpret: bool = True) -> jnp.ndarray:
    n, m = codes.shape
    mk, k = lut.shape
    assert mk == m
    bn = _plan_bn(n, m, k)
    pad = (-n) % bn
    codes_t = jnp.pad(codes, ((0, pad), (0, 0))).T           # [M, n_pad]
    out = pl.pallas_call(
        _kernel,
        grid=((n + pad) // bn,),
        in_specs=[
            pl.BlockSpec((m, bn), lambda i: (0, i)),
            pl.BlockSpec((k, m), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n + pad), jnp.float32),
        interpret=interpret,
    )(codes_t, lut.astype(jnp.float32).T)
    return out[0, :n]


def _kernel_batched(codes_ref, lut_ref, out_ref):
    # One (query, row-block) grid step: this query's LUT stays resident
    # while its row block runs the same lookup as _kernel.
    out_ref[0] = adc_rows(codes_ref[0].astype(jnp.int32), lut_ref[0])


@functools.partial(jax.jit, static_argnames=("interpret",))
def pq_adc_batched_pallas(codes: jnp.ndarray, luts: jnp.ndarray,
                          interpret: bool = True) -> jnp.ndarray:
    """Batched-queries entry: [nq, n, M] codes x [nq, M, K] per-query LUTs
    -> [nq, n] distances. Grid is (queries, row-blocks); each query's rows
    are scored against its own LUT, so rows are batch-invariant. The
    distances come back as [nq, 1, n] so each block's last two dims are a
    whole (1, BN) row, as the TPU's (8, 128) tiling rule requires."""
    nq, n, m = codes.shape
    nq2, m2, k = luts.shape
    assert nq == nq2 and m == m2
    bn = _plan_bn(n, m, k)
    pad = (-n) % bn
    codes_t = jnp.pad(codes, ((0, 0), (0, pad), (0, 0))).transpose(0, 2, 1)
    out = pl.pallas_call(
        _kernel_batched,
        grid=(nq, (n + pad) // bn),
        in_specs=[
            pl.BlockSpec((1, m, bn), lambda i, j: (i, 0, j)),
            pl.BlockSpec((1, k, m), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bn), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((nq, 1, n + pad), jnp.float32),
        interpret=interpret,
    )(codes_t, luts.astype(jnp.float32).transpose(0, 2, 1))
    return out[:, 0, :n]
