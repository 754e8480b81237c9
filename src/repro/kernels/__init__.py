"""Pallas TPU kernels for DecoupleVS's compute hot-spots.

Each kernel directory contains:
  <name>.py — `pl.pallas_call` kernel with explicit BlockSpec VMEM tiling
  ops.py    — public wrapper routed through the dispatch registry
  ref.py    — pure-jnp oracle used by tests/property sweeps

`dispatch.py` is the backend-selection layer (docs/KERNELS.md): a registry
mapping (op, backend) -> implementation, with a per-op `KernelConfig`
resolved once at config time (`auto` -> pallas on TPU, ref on CPU; `pallas`
off-TPU degrades to the interpreter) and an env override `REPRO_KERNELS`.
The search hot path (`core/search/beam.py`) threads the config through
`SearchParams`, so switching backends is a jit-static config change — no
trace-time platform checks anywhere.

Kernels (hot spots of the paper's search path, TPU-adapted per DESIGN.md §2):
  pq_adc     — PQ asymmetric distance via an exact in-VMEM table select;
               `pq_adc_batched` is the batched-queries entry the beam loop
               uses (grid over queries × row-blocks, per-query LUT resident)
  ef_decode  — Elias-Fano fixed-slot adjacency decode (VPU bit ops + rank)
  rerank_l2  — exact L2 re-ranking distances (MXU tiles)
  byteplane  — XOR-delta byte-plane decode of compressed vectors
  beam_step  — the fused beam hop: ADC + top-L candidate merge
"""
from . import byteplane, dispatch, ef_decode, pq_adc, rerank_l2  # noqa: F401
from .dispatch import KernelConfig  # noqa: F401
