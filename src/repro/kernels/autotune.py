"""Persisted per-(op, shape-bucket) backend autotuning for the kernel tier.

BENCH_kernels.json showed the dispatch layer's uniform ``auto`` rule losing
its own benchmark: ``auto`` -> pallas on TPU picked the pallas ``pq_adc``
even where the jnp oracle is 1.5-8x faster, and ``byteplane`` pallas never
beat ref at all. This module replaces the platform-only rule with a
measurement-driven one:

- ``benchmarks/bench_kernels.py`` times every op x backend x size on its
  grid and records the numbers here (``AutotuneCache.record``).
- The cache is persisted as JSON (``save``/``load``) and shipped with the
  package (``kernels/autotune_cache.json``, regenerated by the bench); the
  ``REPRO_AUTOTUNE_CACHE`` env var points at an alternative file.
- An ``auto-tuned`` entry in ``KernelConfig`` resolves per (op,
  shape-bucket) to the backend with the LOWEST measured time —
  so dispatch can never resolve to a bench-losing backend. Shapes are
  bucketed by rounding each dim up to a power of two; an unseen shape
  falls back to the nearest measured bucket of the same op (log-distance),
  then to the op's ``auto`` rule when the op has no measurements at
  all.

Cache entries are platform-keyed: a cache measured on ``cpu`` (where the
pallas column is the interpreter) is ignored on ``tpu`` and vice versa —
interpret-mode correctness timings must never drive compiled-kernel
placement decisions.

Determinism: same shapes -> same bucket -> same argmin (ties break by
backend declaration order, ref first), so a serving config resolved twice
is identical — resolution stays config-time, exactly like ``auto``.
"""
from __future__ import annotations

import json
import math
import os
from pathlib import Path

ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
DEFAULT_CACHE_PATH = Path(__file__).resolve().parent / "autotune_cache.json"
CACHE_VERSION = 1

# Backend preference order for argmin tie-breaks (ref first: the safe,
# deployable-everywhere choice). "off" is the beam_step pseudo-backend —
# the UNFUSED op composition — benched against the fused impls so the
# autotuner genuinely picks the best of {unfused, fused-ref, fused-pallas}.
_ORDER = ("ref", "off", "pallas", "pallas-interpret")


def bucket_dims(**dims) -> dict:
    """Round every dim up to the next power of two (1 stays 1). Two shapes
    in the same bucket are expected to prefer the same backend; the bucket
    string is the cache key."""
    return {k: 1 << max(0, math.ceil(math.log2(max(1, int(v)))))
            for k, v in dims.items()}


def bucket_key(op: str, **dims) -> str:
    b = bucket_dims(**dims)
    return op + "|" + ";".join(f"{k}={v}" for k, v in sorted(b.items()))


def _log_distance(a: dict, b: dict) -> float:
    """Distance between two buckets of the same op: sum of |log2 dim
    ratios| over shared keys, +4 per unshared key (a different dim set is
    a worse match than any 16x size difference on a shared dim)."""
    keys = set(a) | set(b)
    d = 0.0
    for k in keys:
        if k in a and k in b:
            d += abs(math.log2(max(1, a[k])) - math.log2(max(1, b[k])))
        else:
            d += 4.0
    return d


def _parse_key(key: str) -> tuple[str, dict]:
    op, _, rest = key.partition("|")
    dims = {}
    for part in rest.split(";"):
        if part:
            k, _, v = part.partition("=")
            dims[k] = int(v)
    return op, dims


class AutotuneCache:
    """In-memory view of the persisted (op, shape-bucket) -> timings table."""

    def __init__(self, platform: str, entries: dict | None = None):
        self.platform = platform
        # key "op|d1=v1;d2=v2" -> {"us": {backend: µs}}
        self.entries: dict[str, dict] = entries or {}

    # ------------------------------------------------------------- persist
    @classmethod
    def load(cls, path: str | Path | None = None,
             platform: str | None = None) -> "AutotuneCache":
        """Load the cache for ``platform``. A missing/unreadable file or a
        platform mismatch yields an EMPTY cache (resolution falls back to
        the ``auto`` rule) — never an error and never cross-platform
        numbers."""
        if platform is None:
            import jax
            platform = jax.default_backend()
        path = Path(path or os.environ.get(ENV_CACHE) or DEFAULT_CACHE_PATH)
        try:
            doc = json.loads(path.read_text())
        except (OSError, ValueError):
            return cls(platform)
        if doc.get("version") != CACHE_VERSION \
                or doc.get("platform") != platform:
            return cls(platform)
        entries = doc.get("entries", {})
        return cls(platform, {k: dict(v) for k, v in entries.items()})

    def save(self, path: str | Path | None = None) -> Path:
        path = Path(path or os.environ.get(ENV_CACHE) or DEFAULT_CACHE_PATH)
        doc = {
            "version": CACHE_VERSION,
            "platform": self.platform,
            "note": ("per-(op, shape-bucket) measured µs; 'pallas' on a "
                     "cpu-platform cache is the interpreter and is only "
                     "trusted on cpu (see kernels/autotune.py)"),
            "entries": {k: self.entries[k] for k in sorted(self.entries)},
        }
        path.write_text(json.dumps(doc, indent=1, sort_keys=True) + "\n")
        return path

    # -------------------------------------------------------------- record
    def record(self, op: str, backend: str, us: float, **dims) -> None:
        key = bucket_key(op, **dims)
        entry = self.entries.setdefault(key, {"us": {}})
        # Keep the best (lowest) time seen for the bucket: re-benching a
        # bucket with a different concrete shape must not let one noisy
        # slow sample evict a demonstrated-fast backend.
        prev = entry["us"].get(backend)
        entry["us"][backend] = round(us, 3) if prev is None \
            else min(prev, round(us, 3))

    # -------------------------------------------------------------- choose
    def _argmin(self, entry: dict) -> str:
        us = entry["us"]
        return min(sorted(us, key=lambda b: _ORDER.index(b)
                          if b in _ORDER else len(_ORDER)),
                   key=lambda b: us[b])

    def best(self, op: str, dims: dict | None = None,
             fallback: str = "ref") -> str:
        """Backend with the lowest measured time for (op, bucket-of-dims).

        Lookup order: exact bucket -> nearest measured bucket of the same
        op (log-distance over dims; deterministic tie-break by key) ->
        global argmin over all of the op's buckets when no dims are given
        -> ``fallback`` when the op has no measurements. The argmin can by
        construction never return a backend that lost its own bucket's
        bench."""
        mine = {k: v for k, v in self.entries.items()
                if _parse_key(k)[0] == op}
        if not mine:
            return fallback
        if dims:
            key = bucket_key(op, **dims)
            if key in mine:
                return self._argmin(mine[key])
            want = bucket_dims(**dims)
            near = min(sorted(mine),
                       key=lambda k: (_log_distance(want,
                                                    _parse_key(k)[1]), k))
            return self._argmin(mine[near])
        # No shape hint (e.g. resolve_kernels before the index is known):
        # majority vote across the op's measured buckets, ties to _ORDER.
        votes: dict[str, int] = {}
        for k in sorted(mine):
            b = self._argmin(mine[k])
            votes[b] = votes.get(b, 0) + 1
        return max(sorted(votes, key=lambda b: _ORDER.index(b)
                          if b in _ORDER else len(_ORDER)),
                   key=lambda b: votes[b])
