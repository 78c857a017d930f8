"""Pallas TPU kernels for the performance-critical compute layers.

Each kernel has three pieces:
  <name>.py — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (Mosaic on TPU, interpret elsewhere:
              backend.py makes that choice for every kernel)
  ref.py    — pure-jnp oracle used for allclose validation and as the
              production XLA path where the kernel isn't warranted

Kernels:
  hydro_rhs        — fused Reconstruct+Flux over aggregated sub-grid slots
                     (slot-grid and slot-lane layouts)
  grouped_gemm     — MoE expert-aggregated GEMM with dead-tile skipping
  decode_attention — bucketed flash-decode GQA attention for the serving
                     engine's aggregated request batches
"""
from repro.kernels.ops import decode_attention, grouped_gemm, hydro_rhs

__all__ = ["decode_attention", "grouped_gemm", "hydro_rhs"]
