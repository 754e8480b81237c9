"""Base vectors and query pools, generated from the seed.

A copy of the program's synthetic generators (``repro.data.synthetic``:
``make_vector_dataset`` and ``make_queries``), kept here so that a change to
the program cannot change the benchmark's data. For the same kind, size and
seed it returns the same arrays as the original.
"""
from __future__ import annotations

import numpy as np


def make_vectors(kind: str, n: int, dim: int, seed: int) -> np.ndarray:
    rng = np.random.default_rng(seed)
    if kind == "sift-like":
        # Gradient-histogram style: nonnegative, many near-zero bins, a few
        # strong bins per dimension; per-dimension scale varies.
        scale = rng.uniform(1.5, 12.0, size=dim)
        raw = rng.gamma(shape=0.6, scale=scale[None, :], size=(n, dim))
        return np.clip(raw, 0, 255).astype(np.uint8)
    if kind == "spacev-like":
        center = rng.integers(-30, 30, size=dim)
        raw = center[None, :] + rng.normal(0, 24.0, size=(n, dim))
        return np.clip(raw, -128, 127).astype(np.int8)
    if kind == "prop-like":
        spectrum = rng.uniform(0.2, 1.0, size=dim) ** 2
        raw = rng.normal(0, 1.0, size=(n, dim)) * spectrum[None, :]
        raw /= np.linalg.norm(raw, axis=1, keepdims=True) + 1e-12
        return np.round(raw, 3).astype(np.float32)
    raise ValueError(f"unknown dataset kind {kind!r}")


def make_queries(kind: str, n: int, dim: int, seed: int) -> np.ndarray:
    """Queries from the same distribution under a held-out seed."""
    return make_vectors(kind, n, dim, seed + 10_000)
