"""Kernel dispatch layer: one registry from (op, backend) to implementation.

The seed picked per-op between the Pallas kernel and the jnp oracle by
calling ``jax.default_backend()`` *inside* each op — a trace-time check that
is wrong under ``jit`` on mixed-backend meshes and invisible to callers.
This module moves the decision to **config time**: a ``KernelConfig`` names
a backend per op, ``auto``/``auto-tuned`` entries are resolved exactly once
(when the config is built — never at trace time), and the resolved config is
threaded through ``SearchParams`` / ``BatchedSearcher`` as static jit state,
so every op call inside the search program is a direct table lookup.

Backends:

    ref               pure-jnp oracle (the deployable XLA CPU path)
    pallas            compiled ``pallas_call`` (TPU)
    pallas-interpret  the same kernel run by the Pallas interpreter —
                      correct everywhere, used to validate kernels on CPU
    off               (``beam_step`` only) the unfused op composition in
                      the hot path — the pre-fusion program

Requested values additionally allow ``auto`` and ``auto-tuned``. Resolution
(once, at config time):

- ``auto`` -> ``pallas`` on TPU else ``ref``, for every op: on the chip
  the compiled kernels run, everywhere else the jnp oracle does.
- ``auto-tuned`` -> the backend with the lowest MEASURED time for the
  (op, shape-bucket) in the persisted autotune cache (``autotune.py``,
  regenerated by ``benchmarks/bench_kernels.py``; ``REPRO_AUTOTUNE_CACHE``
  overrides the path), falling back to the ``auto`` rule when the op has
  no measurements on this platform. By construction it can never
  resolve to a backend that lost its bucket's bench.
- ``pallas`` off-TPU degrades to ``pallas-interpret`` (a compiled Mosaic
  kernel only exists on TPU).

An unresolved ``auto``/``auto-tuned`` reaching ``get_impl`` is a bug and
raises.

Env override: ``REPRO_KERNELS=ref|pallas|auto|auto-tuned`` (also accepts
``pallas-interpret``) sets the backend for every op when the caller does
not pass an explicit config (``SearchParams(kernels=None)``).
"""
from __future__ import annotations

import functools
import os
from typing import Callable, NamedTuple

import jax

BACKENDS = ("ref", "pallas", "pallas-interpret")
REQUESTED = BACKENDS + ("auto", "auto-tuned", "off")
ENV_VAR = "REPRO_KERNELS"
# The op list is KernelConfig._fields; the registry keys (which add the
# batched pq_adc entry, keyed off the pq_adc config field) are authoritative.


class KernelConfig(NamedTuple):
    """Per-op backend selection. A plain NamedTuple of strings: hashable, so
    it rides inside ``SearchParams`` as static jit state (changing backends
    recompiles the search program — that is the point).

    ``beam_step`` selects the FUSED hop kernel (ADC + top-L merge in one
    launch, ``kernels/beam_step/``); ``"off"`` keeps the unfused op
    composition. Its ``ref`` backend is op-for-op identical jnp to the
    unfused path, so ``auto`` resolving it to ``ref`` off-TPU changes
    nothing but the call structure."""
    pq_adc: str = "auto"
    ef_decode: str = "auto"
    rerank_l2: str = "auto"
    byteplane: str = "auto"
    beam_step: str = "auto"

    def resolve(self, platform: str | None = None,
                shapes: dict | None = None) -> "KernelConfig":
        """Map ``auto``/``auto-tuned``/off-platform requests to concrete
        backends. Call at config time. Idempotent: ``ref``/
        ``pallas-interpret``/``off`` are fixed points (short-circuited
        without a platform query); ``pallas`` re-checks the platform so it
        degrades to the interpreter off-TPU. ``shapes`` optionally maps op
        name -> dims dict so ``auto-tuned`` entries resolve per
        shape-bucket; without it they resolve to the op's majority-winner
        bucket (see ``autotune.AutotuneCache.best``)."""
        if all(b in ("ref", "pallas-interpret", "off") for b in self):
            return self
        platform = platform or jax.default_backend()
        cache = None
        if any(b == "auto-tuned" for b in self):
            from . import autotune
            cache = autotune.AutotuneCache.load(platform=platform)
        shapes = shapes or {}
        return KernelConfig(*(
            resolve_backend(b, platform, op=op, cache=cache,
                            dims=shapes.get(op))
            for op, b in zip(self._fields, self)))

    @property
    def is_resolved(self) -> bool:
        """True when no entry is ``auto``/``auto-tuned`` (safe to hand to
        ``get_impl``). Note a ``pallas`` entry still degrades per-platform
        in ``resolve()`` — resolve at config time, don't rely on this
        alone."""
        return all(b in BACKENDS + ("off",) for b in self)


def _auto_rule(platform: str) -> str:
    return "pallas" if platform == "tpu" else "ref"


def resolve_backend(requested: str, platform: str | None = None,
                    op: str | None = None, cache=None,
                    dims: dict | None = None) -> str:
    """One op's requested backend -> concrete backend for ``platform``.

    ``op`` enables the per-op rules (the autotune cache lookup, the
    ``off`` pseudo-backend); without it only the platform rule applies.
    ``cache`` is an ``autotune.AutotuneCache`` (loaded lazily when
    ``auto-tuned`` is requested and none is passed)."""
    if requested not in REQUESTED:
        raise ValueError(
            f"unknown kernel backend {requested!r}; "
            f"expected one of {REQUESTED}")
    if requested == "off":
        if op not in (None, "beam_step"):
            raise ValueError(
                f"backend 'off' only applies to the beam_step op, not {op!r}"
                " — every other op is always on some backend")
        return "off"
    if requested in ("ref", "pallas-interpret"):
        return requested
    platform = platform or jax.default_backend()
    if requested == "auto":
        return _auto_rule(platform)
    if requested == "auto-tuned":
        if cache is None:
            from . import autotune
            cache = autotune.AutotuneCache.load(platform=platform)
        best = cache.best(op or "", dims, fallback=_auto_rule(platform))
        # Degrade a measured 'pallas' pick off-TPU (it was measured as the
        # interpreter there — see autotune platform keying) and keep 'off'
        # as-is for beam_step.
        return best if best == "off" else resolve_backend(best, platform, op)
    if requested == "pallas" and platform != "tpu":
        return "pallas-interpret"
    return requested


def from_env(default: str = "auto",
             platform: str | None = None) -> KernelConfig:
    """Uniform config from ``REPRO_KERNELS``, resolved (config time)."""
    req = os.environ.get(ENV_VAR, default).strip() or default
    return KernelConfig(req, req, req, req, req).resolve(platform)


def default_config() -> KernelConfig:
    """The config used when a caller passes ``kernels=None``."""
    return from_env()


# --------------------------------------------------------------- registry
@functools.lru_cache(maxsize=1)
def _registry() -> dict[tuple[str, str], Callable]:
    # Imports are local: implementation modules must not import dispatch
    # back (ops.py does), and building the table lazily keeps module import
    # cycle-free.
    from .beam_step.beam_step import beam_step_pallas
    from .beam_step.ref import beam_step_ref
    from .byteplane.byteplane import byteplane_decode_pallas
    from .byteplane.ref import byteplane_decode_ref
    from .ef_decode.ef_decode import ef_decode_pallas
    from .ef_decode.ref import ef_decode_ref
    from .pq_adc.pq_adc import pq_adc_batched_pallas, pq_adc_pallas
    from .pq_adc.ref import pq_adc_batched_ref, pq_adc_ref

    from .rerank_l2.ref import rerank_l2_ref
    from .rerank_l2.rerank_l2 import rerank_l2_pallas

    def pallas(fn, interpret):
        return functools.partial(fn, interpret=interpret)

    table: dict[tuple[str, str], Callable] = {}
    for op, ref, kern in (
            ("pq_adc", pq_adc_ref, pq_adc_pallas),
            ("pq_adc_batched", pq_adc_batched_ref, pq_adc_batched_pallas),
            ("ef_decode", ef_decode_ref, ef_decode_pallas),
            ("rerank_l2", rerank_l2_ref, rerank_l2_pallas),
            ("byteplane", byteplane_decode_ref, byteplane_decode_pallas),
            ("beam_step", beam_step_ref, beam_step_pallas)):
        table[op, "ref"] = ref
        table[op, "pallas"] = pallas(kern, False)
        table[op, "pallas-interpret"] = pallas(kern, True)
    return table


def get_impl(op: str, backend: str) -> Callable:
    """(op, concrete backend) -> implementation. Raises on ``auto``: an
    unresolved config reaching dispatch means selection leaked past config
    time (exactly the trace-time bug this layer removes)."""
    if backend in ("auto", "auto-tuned"):
        raise RuntimeError(
            f"unresolved {backend!r} backend reached dispatch for op "
            f"{op!r}; call KernelConfig.resolve() at config time")
    if backend == "off":
        raise RuntimeError(
            f"backend 'off' reached dispatch for op {op!r}: 'off' means "
            "the UNFUSED composition — the hot path must branch on it "
            "before calling dispatch (core/search/beam.py does)")
    try:
        return _registry()[op, backend]
    except KeyError:
        raise KeyError(f"no implementation registered for "
                       f"op={op!r} backend={backend!r}") from None


def register(op: str, backend: str, fn: Callable) -> None:
    """Extension hook: register/override an implementation."""
    _registry()[op, backend] = fn


# ------------------------------------------------------------- public ops
# Thin wrappers so hot-path call sites read as ops, not table lookups.
# ``cfg`` must be a resolved KernelConfig (None -> env default).

def _cfg(cfg: KernelConfig | None) -> KernelConfig:
    return default_config() if cfg is None else cfg


def pq_adc(codes, lut, cfg: KernelConfig | None = None):
    """[n, M] codes x [M, K] LUT -> [n] ADC distances."""
    cfg = _cfg(cfg)
    return get_impl("pq_adc", cfg.pq_adc)(codes, lut)


def pq_adc_batched(codes, luts, cfg: KernelConfig | None = None):
    """[nq, n, M] codes x [nq, M, K] per-query LUTs -> [nq, n]."""
    cfg = _cfg(cfg)
    return get_impl("pq_adc_batched", cfg.pq_adc)(codes, luts)


def ef_decode(slots, r_max: int, universe: int,
              cfg: KernelConfig | None = None):
    """[B, W] uint32 slots -> (neighbors [B, r_max], counts [B])."""
    cfg = _cfg(cfg)
    return get_impl("ef_decode", cfg.ef_decode)(slots, r_max, universe)


def rerank_l2(queries, cands, cfg: KernelConfig | None = None):
    """[Q, D] queries x [Q, C, D] candidates -> squared L2 [Q, C]."""
    cfg = _cfg(cfg)
    return get_impl("rerank_l2", cfg.rerank_l2)(queries, cands)


def byteplane_decode(packed, base, cfg: KernelConfig | None = None):
    """[n, V] uint8 XOR [V] uint8 base -> [n, V] uint8."""
    cfg = _cfg(cfg)
    return get_impl("byteplane", cfg.byteplane)(packed, base)


def beam_step(codes, luts, cand_ids, cand_d, new_ids,
              cfg: KernelConfig | None = None):
    """Fused hop tail: [nq, E, M] codes x [nq, M, K] LUTs merged into the
    [nq, L] candidate list -> (cand_ids', cand_d', top_idx)."""
    cfg = _cfg(cfg)
    return get_impl("beam_step", cfg.beam_step)(
        codes, luts, cand_ids, cand_d, new_ids)
