"""Oracle for PQ asymmetric distance computation (ADC)."""
import jax
import jax.numpy as jnp


def sum_in_order(g: jnp.ndarray) -> jnp.ndarray:
    """Sum over the last axis, strictly left to right. The Pallas kernels
    add the M sub-distances in this order too, so ADC distances agree bit
    for bit across backends (a tree-shaped reduce would round differently
    and could flip near-tied candidates in the beam)."""
    acc = g[..., 0]
    for j in range(1, g.shape[-1]):
        acc = acc + g[..., j]
    return acc


def pq_adc_ref(codes: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    """dist[i] = sum_m lut[m, codes[i, m]].

    codes: [n, M] integer (uint8/int32), lut: [M, K] float32 -> [n] float32.
    """
    m = lut.shape[0]
    return sum_in_order(lut[jnp.arange(m)[None, :], codes.astype(jnp.int32)])


def pq_adc_batched_ref(codes: jnp.ndarray, luts: jnp.ndarray) -> jnp.ndarray:
    """Batched-queries oracle: [nq, n, M] x [nq, M, K] -> [nq, n]."""
    return jax.vmap(pq_adc_ref)(codes, luts)
