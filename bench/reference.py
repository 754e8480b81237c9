"""The plain reference and the comparison that decides ``correct``.

The reference is exact brute-force search over the base the benchmark
generated, in float64 NumPy for the returned ids and in integer arithmetic
on the default device for each query's exact K-th distance: it imports nothing of the program and reads
nothing the program built. Every answer the window's calls returned is
held to the configuration's guarantees:

- ``bad_answers``: answers that are not K distinct ids of base rows with
  finite distances in ascending order. Exact comparison, limit 0.
- ``dist_err``: over the well-formed answers, the largest gap between a
  returned distance and the exact squared L2 of that id. The data are
  integers and every squared L2 is below 2^24, so an exact re-rank in
  float32 returns it exactly. Exact comparison, limit 0.
- ``recall_miss``: 1 - recall@K against the exact top-K, where a returned
  id whose exact distance is no larger than the exact K-th distance counts
  as a hit (ties are hits). Over every answer of the window.
"""
from __future__ import annotations

import numpy as np


def answers(window) -> tuple:
    """(pool rows, ids, dists) of every answer of the window, in order."""
    if not window.calls:
        return np.zeros(0, np.int64), np.zeros((0, 0)), np.zeros((0, 0))
    return (np.concatenate([c.rows for c in window.calls]),
            np.concatenate([c.ids for c in window.calls]),
            np.concatenate([c.dists for c in window.calls]))


def exact_sq_l2(base: np.ndarray, queries: np.ndarray,
                ids: np.ndarray) -> np.ndarray:
    """float64 squared L2 between each query and each of its ids."""
    x = base[ids].astype(np.float64)                       # [m, K, d]
    return ((x - queries[:, None, :].astype(np.float64)) ** 2).sum(-1)


def kth_nearest(base: np.ndarray, queries: np.ndarray, k: int,
                block: int = 256) -> np.ndarray:
    """The exact k-th smallest squared L2 from each query to the base, on
    the default device, over blocks of queries. The data are integers
    below 256 (exact in bfloat16) and every dot product is below 2^24
    (exact in the float32 accumulator), so with the norms added in int32
    every distance is exact."""
    import jax
    import jax.numpy as jnp

    if not (np.issubdtype(base.dtype, np.integer)
            and np.abs(base).max(initial=0) < 256
            and np.array_equal(queries, np.round(queries))
            and np.abs(queries).max(initial=0) < 256):
        raise ValueError("the exact reference needs integer data below 256")
    b = jnp.asarray(base, jnp.bfloat16)
    bb = jnp.sum(jnp.square(jnp.asarray(base, jnp.int32)), axis=1)

    @jax.jit
    def kth(q, b, bb):
        cross = jnp.dot(q, b.T, preferred_element_type=jnp.float32)
        qq = jnp.sum(jnp.square(q.astype(jnp.int32)), axis=1)
        d = qq[:, None] + bb[None, :] - 2 * cross.astype(jnp.int32)
        return -jax.lax.top_k(-d, k)[0][:, k - 1]

    m = len(queries)
    block = min(block, m)
    out = np.empty(m, np.float64)
    for s in range(0, m, block):
        s = min(s, m - block)               # one shape for every block
        out[s:s + block] = np.asarray(
            kth(jnp.asarray(queries[s:s + block], jnp.bfloat16), b, bb))
    return out


def well_formed(ids: np.ndarray, dists: np.ndarray, n: int,
                k: int) -> np.ndarray:
    """Per answer: K ids of base rows, distinct, with finite distances in
    ascending order."""
    ok = (ids.shape[1] == k) & np.all((ids >= 0) & (ids < n), 1)
    ok &= np.all(np.isfinite(dists), 1)
    ok &= np.all(np.diff(dists, axis=1) >= 0, 1)
    srt = np.sort(ids, 1)
    ok &= np.all(srt[:, 1:] != srt[:, :-1], 1)
    return ok


def compare(base: np.ndarray, pool: np.ndarray, window, config: dict) -> dict:
    rows, ids, dists = answers(window)
    k, n = config["k"], len(base)
    out = {"answers": len(rows), "bad_answers": 0, "dist_err": 0.0,
           "recall": float("nan"), "recall_miss": 1.0, "recall_answers": 0}
    if not len(rows):
        return out
    ok = well_formed(ids, dists, n, k)
    out["bad_answers"] = int((~ok).sum())
    rows, ids, dists = rows[ok], ids[ok], dists[ok]
    if not len(rows):
        return out
    exact = exact_sq_l2(base, pool[rows], ids)
    out["dist_err"] = float(np.abs(dists.astype(np.float64) - exact).max())
    uniq, inv = np.unique(rows, return_inverse=True)
    kth = kth_nearest(base, pool[uniq], k)[inv]
    hits = (exact <= kth[:, None]).sum()
    out["recall"] = float(hits / (len(rows) * k))
    out["recall_miss"] = 1.0 - out["recall"]
    out["recall_answers"] = len(rows)
    return out
