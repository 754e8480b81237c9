"""Product quantization (Jegou et al. [18]) — the in-memory lossy codes that
DiskANN-family systems (and DecoupleVS, §3.1) keep in DRAM/HBM to steer graph
traversal without touching full-precision vectors.

Pure numpy/jnp: k-means codebook training, encoding, and asymmetric distance
computation (ADC) via per-query lookup tables. The TPU hot path lives in
``repro.kernels.pq_adc`` (an exact table select in VMEM); ``adc_lookup_np``
here is the semantics oracle.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax.numpy as jnp


@dataclass
class PQCodebook:
    centroids: np.ndarray   # [M, K, dsub] float32
    dim: int

    @property
    def n_subspaces(self) -> int:
        return self.centroids.shape[0]

    @property
    def n_centroids(self) -> int:
        return self.centroids.shape[1]


def _sq_dists(sub: np.ndarray, cents: np.ndarray) -> np.ndarray:
    """[n, dsub] x [K, dsub] -> [n, K] squared L2, accumulated over dsub
    in place on one [n, K] buffer (no [n, K, dsub] temporary: ~6x faster
    at 2^20 rows). For dsub < 8 the sum order is numpy's own, so the
    result equals ``((sub[:, None] - cents[None]) ** 2).sum(-1)`` exactly."""
    acc = sub[:, None, 0] - cents[None, :, 0]
    acc *= acc
    for j in range(1, sub.shape[1]):
        t = sub[:, None, j] - cents[None, :, j]
        t *= t
        acc += t
    return acc


def train_pq(vectors: np.ndarray, m: int = 8, k: int = 256, iters: int = 8,
             seed: int = 0, sample: int = 20_000) -> PQCodebook:
    """Train M sub-codebooks of K centroids by Lloyd's k-means."""
    x = np.asarray(vectors, dtype=np.float32)
    n, d = x.shape
    if d % m:
        raise ValueError(f"dim {d} not divisible by m {m}")
    dsub = d // m
    rng = np.random.default_rng(seed)
    if n > sample:
        x = x[rng.choice(n, size=sample, replace=False)]
        n = sample
    k_eff = min(k, n)
    cents = np.zeros((m, k, dsub), dtype=np.float32)
    for mi in range(m):
        sub = x[:, mi * dsub:(mi + 1) * dsub]
        c = sub[rng.choice(n, size=k_eff, replace=False)].copy()
        for _ in range(iters):
            assign = _sq_dists(sub, c).argmin(1)
            for ci in range(k_eff):
                mask = assign == ci
                if mask.any():
                    c[ci] = sub[mask].mean(0)
        cents[mi, :k_eff] = c
        if k_eff < k:  # duplicate to fill the table (tiny datasets)
            cents[mi, k_eff:] = c[rng.integers(0, k_eff, size=k - k_eff)]
    return PQCodebook(centroids=cents, dim=d)


def encode_pq(vectors: np.ndarray, cb: PQCodebook, chunk: int = 512) -> np.ndarray:
    """Encode [n, d] -> [n, M] uint8 codes."""
    x = np.asarray(vectors, dtype=np.float32)
    n, d = x.shape
    m, k, dsub = cb.centroids.shape
    codes = np.zeros((n, m), dtype=np.uint8)
    for i in range(0, n, chunk):
        xi = x[i:i + chunk]
        for mi in range(m):
            sub = xi[:, mi * dsub:(mi + 1) * dsub]
            codes[i:i + chunk, mi] = _sq_dists(
                sub, cb.centroids[mi]).argmin(1).astype(np.uint8)
    return codes


def build_lut(query: np.ndarray, cb: PQCodebook) -> np.ndarray:
    """Per-query ADC lookup table [M, K] float32 of squared sub-distances."""
    q = np.asarray(query, dtype=np.float32)
    m, k, dsub = cb.centroids.shape
    qs = q.reshape(m, 1, dsub)
    return ((qs - cb.centroids) ** 2).sum(-1).astype(np.float32)


def adc_lookup_np(codes: np.ndarray, lut: np.ndarray) -> np.ndarray:
    """Oracle ADC: dist[i] = sum_m lut[m, codes[i, m]]."""
    m = lut.shape[0]
    return lut[np.arange(m)[None, :], codes].sum(-1)


def build_lut_jnp(query: jnp.ndarray, centroids: jnp.ndarray) -> jnp.ndarray:
    """jnp LUT builder (device path). centroids [M, K, dsub].

    The dsub squares are added left to right as explicit adds, which XLA
    does not reorder: a search program computes the same LUT bits whatever
    it fuses the LUT into, so programs that differ only in kernel backend
    score identical distances."""
    m, k, dsub = centroids.shape
    sq = (query.reshape(m, 1, dsub) - centroids) ** 2
    acc = sq[..., 0]
    for j in range(1, dsub):
        acc = acc + sq[..., j]
    return acc
