"""Elias-Fano fixed-slot decode kernel.

The device-resident compressed graph stores each adjacency list in a
fixed-size slot (worst-case bound 2R + R*ceil(log2(N/R)) bits, §3.3/§3.4), so
vertex id -> slot address is direct. Decode = fixed-width low-bit unpack +
select-in-bitmap for the high bits.

TPU adaptation (DESIGN.md §2): CPU implementations use sequential rank/select
structures; here every step is a whole-tile VPU/MXU op on [BL, ·] rows, and
no op gathers:
  - the low bits of entry j sit at the static bit offset j*l, so each
    entry's two candidate words are picked by a compare-select over the
    slot's low words, then shifted and masked;
  - the unary high bitmap is expanded to one bit per lane, its inclusive
    prefix count is a matmul with an upper-triangular 0/1 matrix (exact:
    0/1 operands, integer sums), and the (j+1)-th set bit sits at
    pos(j) = #{b : prefix[b] <= j}, one compare + lane count per j.

Slots are uint32 words; the kernel reads them as int32 bit patterns and
uses logical shifts.

Tiling: grid over blocks of BL slots (BL <= 128, a multiple of 8); per step
VMEM holds the slot block [BL, W], the [BL, nbits] bitmap rows and the
[nbits, nbits] triangle (nbits = the bitmap rounded up to 128 lanes).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.codec.elias_fano import slot_layout

BL_MAX = 128  # slots per grid step (upper bound; small batches use fewer)


def _make_kernel(r_max: int, universe: int):
    l, lw, hb, total = slot_layout(r_max, universe)
    nbits = -(-hb * 32 // 128) * 128
    low_mask = (1 << l) - 1 if l < 32 else -1

    def kernel(slots_ref, nbr_ref, cnt_ref):
        w = slots_ref[...]                                    # [BL, total]
        bl = w.shape[0]
        cnt_ref[...] = w[:, 0:1]
        j = jax.lax.broadcasted_iota(jnp.int32, (bl, r_max), 1)
        # ---- low bits: entry j spans bits [j*l, j*l + l) of the low words
        if l:
            start = j * l
            word, off = start >> 5, start & 31
            g0 = g1 = jnp.zeros((bl, r_max), jnp.int32)
            for wi in range(lw):
                col = w[:, 1 + wi:2 + wi]
                g0 = jnp.where(word == wi, col, g0)
                g1 = jnp.where(word + 1 == wi, col, g1)
            lo = jax.lax.shift_right_logical(g0, off)
            hi = jnp.where(off > 0, jax.lax.shift_left(g1, (32 - off) & 31), 0)
            low = (lo | hi) & low_mask
        else:
            low = jnp.zeros((bl, r_max), jnp.int32)
        # ---- high bits: one bitmap bit per lane, then rank-select
        b = jax.lax.broadcasted_iota(jnp.int32, (bl, nbits), 1)
        hw = jnp.zeros((bl, nbits), jnp.int32)
        for wi in range(hb):
            hw = jnp.where((b >> 5) == wi, w[:, 1 + lw + wi:2 + lw + wi], hw)
        bits = jax.lax.shift_right_logical(hw, b & 31) & 1
        tri = (jax.lax.broadcasted_iota(jnp.int32, (nbits, nbits), 0)
               <= jax.lax.broadcasted_iota(jnp.int32, (nbits, nbits), 1))
        prefix = jnp.dot(bits.astype(jnp.bfloat16), tri.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32
                         ).astype(jnp.int32)                  # [BL, nbits]

        def select(jj, pos):
            at = jnp.sum((prefix <= jj).astype(jnp.int32), axis=1,
                         keepdims=True)                       # [BL, 1]
            return jnp.where(j == jj, at, pos)
        pos = jax.lax.fori_loop(0, r_max, select,
                                jnp.zeros((bl, r_max), jnp.int32))
        nbr_ref[...] = jax.lax.shift_left(pos - j, l) | low

    return kernel, total


@functools.partial(jax.jit, static_argnames=("r_max", "universe", "interpret"))
def ef_decode_pallas(slots: jnp.ndarray, r_max: int, universe: int,
                     interpret: bool = True):
    b, total = slots.shape
    kernel, total_expected = _make_kernel(r_max, universe)
    assert total == total_expected, (total, total_expected)
    bl = min(BL_MAX, b + (-b) % 8)
    pad = (-b) % bl
    words = jax.lax.bitcast_convert_type(
        jnp.pad(slots.astype(jnp.uint32), ((0, pad), (0, 0))), jnp.int32)
    nbrs, cnts = pl.pallas_call(
        kernel,
        grid=((b + pad) // bl,),
        in_specs=[pl.BlockSpec((bl, total), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((bl, r_max), lambda i: (i, 0)),
                   pl.BlockSpec((bl, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b + pad, r_max), jnp.int32),
                   jax.ShapeDtypeStruct((b + pad, 1), jnp.int32)],
        interpret=interpret,
    )(words)
    return nbrs[:b], cnts[:b, 0]
