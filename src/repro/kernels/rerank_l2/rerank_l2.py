"""Exact L2 re-ranking distances on the MXU.

||q - x||^2 = ||q||^2 + ||x||^2 - 2 q.x : the cross term and the candidate
norms are matmuls, so the re-ranking phase (§3.4 phase 2) rides the
systolic array instead of the VPU. Both run at ``Precision.HIGHEST``: the
MXU's default single bf16 pass would round every float32 coordinate to 8
bits and reorder near-tied candidates; at HIGHEST the products of
integer-valued coordinates (uint8 / int8 corpora) are exact and their sums
stay exact below 2^24, so the distances equal the oracle's bit for bit.

Tiling: grid (queries, C-blocks); one query row [1, D] against a block of
its candidates [BC, D] per step, written back as a lane-dense [1, BC] row
of an [Q, 1, C] array (the TPU's (8, 128) tiling rule on the last two
block dims). D is padded to a 128 multiple; C is padded to a multiple of 8
and, up to C_WHOLE candidates, taken whole in one block, otherwise in
blocks of BC_MAX.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

C_WHOLE = 1024   # candidates per query taken in a single block
BC_MAX = 512     # block size beyond that


def _kernel(q_ref, x_ref, out_ref):
    q = q_ref[0]                                          # [1, D]
    x = x_ref[0]                                          # [BC, D]
    nt = (((1,), (1,)), ((), ()))                         # contract D with D
    mm = functools.partial(jax.lax.dot_general, dimension_numbers=nt,
                           precision=jax.lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32)
    cross = mm(q, x)                                      # [1, BC]
    xx = mm(jnp.ones_like(q), x * x)                      # [1, BC]
    qq = jnp.sum(q * q, axis=1, keepdims=True)            # [1, 1]
    out_ref[0] = qq + xx - 2.0 * cross


@functools.partial(jax.jit, static_argnames=("interpret",))
def rerank_l2_pallas(queries: jnp.ndarray, cands: jnp.ndarray,
                     interpret: bool = True) -> jnp.ndarray:
    qn, d = queries.shape
    qn2, c, d2 = cands.shape
    assert qn == qn2 and d == d2
    cp = c + (-c) % 8
    bc = cp if cp <= C_WHOLE else BC_MAX
    cp += (-cp) % bc
    dp = d + (-d) % 128
    q_pad = jnp.pad(queries.astype(jnp.float32), ((0, 0), (0, dp - d)))
    x_pad = jnp.pad(cands.astype(jnp.float32),
                    ((0, 0), (0, cp - c), (0, dp - d)))
    out = pl.pallas_call(
        _kernel,
        grid=(qn, cp // bc),
        in_specs=[
            pl.BlockSpec((1, 1, dp), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, bc, dp), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bc), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((qn, 1, cp), jnp.float32),
        interpret=interpret,
    )(q_pad[:, None], x_pad)
    return out[:, 0, :c]
