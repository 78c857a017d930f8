"""Grouped (expert-aggregated) GEMM Pallas kernel.

This is the paper's strategy 3 applied at the kernel level inside MoE layers:
each expert's GEMM over its routed tokens is a fine-grained task (for DBRX,
16 experts x top-4 means each expert sees ~1/4 of the tokens — small, skewed
matmuls); launching them separately starves the MXU exactly like Octo-Tiger's
8^3 sub-grid kernels starved the A100.  The aggregated launch fuses all E
per-expert GEMMs into one kernel over a (expert, token-tile, n-tile, k-tile)
grid, with per-expert valid-row masking — the "slot index" the paper adds to
its aggregated kernels is the expert id here.

Capacity layout: ``x (E, C, K) @ w (E, K, N) -> y (E, C, N)`` with
``group_len (E,)`` valid rows; tiles whose token range lies entirely beyond
``group_len[e]`` skip the MXU work (ragged/dropless behavior within a static
shape — the bucketed-static-shape adaptation of dynamic aggregation).

Block shapes default to MXU-aligned (128, 512, 128) tiles; the fp32
accumulator lives in VMEM scratch across the k-loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import pallas_call


def _gg_kernel(gl_ref, x_ref, w_ref, o_ref, acc_ref, *, n_k: int, bc: int):
    ci = pl.program_id(1)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # tile is live if any of its rows belong to the expert's group
    live = ci * bc < gl_ref[0]

    @pl.when(live)
    def _mac():
        acc_ref[...] += jnp.dot(x_ref[0], w_ref[0],
                                preferred_element_type=jnp.float32)

    @pl.when(ki == n_k - 1)
    def _store():
        rows = ci * bc + jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
        mask = rows < gl_ref[0]
        o_ref[0] = jnp.where(mask, acc_ref[...], 0.0).astype(o_ref.dtype)


def grouped_gemm(x: jax.Array, w: jax.Array, group_len: jax.Array, *,
                 bc: int = 128, bn: int = 128, bk: int = 512) -> jax.Array:
    """x: (E, C, K) @ w: (E, K, N) -> (E, C, N), rows masked by group_len."""
    e, c, k = x.shape
    n = w.shape[2]
    bc, bn, bk = min(bc, c), min(bn, n), min(bk, k)
    assert c % bc == 0 and n % bn == 0 and k % bk == 0, (x.shape, w.shape)
    n_k = k // bk
    grid = (e, c // bc, n // bn, n_k)
    return pallas_call(
        functools.partial(_gg_kernel, n_k=n_k, bc=bc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda ei, ci, ni, ki: (ei,)),
            pl.BlockSpec((1, bc, bk), lambda ei, ci, ni, ki: (ei, ci, ki)),
            pl.BlockSpec((1, bk, bn), lambda ei, ci, ni, ki: (ei, ki, ni)),
        ],
        out_specs=pl.BlockSpec((1, bc, bn), lambda ei, ci, ni, ki: (ei, ci, ni)),
        out_shape=jax.ShapeDtypeStruct((e, c, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bn), jnp.float32)],
    )(group_len, x, w)
