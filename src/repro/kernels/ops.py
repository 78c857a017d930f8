"""Jit'd public wrappers for the Pallas kernels.

On TPU the kernels compile natively; everywhere else they run in interpret
mode (``repro.kernels.backend`` decides from the platform a program is
lowered for), which is how correctness is validated in this repository.
``use_pallas=False`` routes to the pure-jnp oracle — the "Kokkos vs
native" portability axis of the paper, reproduced as Pallas-vs-XLA
(benchmarks/portability.py).
"""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import ref as _ref
from repro.kernels.decode_attention import decode_attention as _decode_pallas
from repro.kernels.grouped_gemm import grouped_gemm as _gg_pallas
from repro.kernels.hydro_rhs import hydro_rhs_pallas


@partial(jax.jit, static_argnames=("h", "gamma", "ghost", "subgrid",
                                   "layout", "use_pallas"))
def hydro_rhs(u_slots, *, h, gamma, ghost, subgrid, layout="slot_grid",
              use_pallas=True):
    if not use_pallas:
        return _ref.hydro_rhs_ref(u_slots, h=h, gamma=gamma, ghost=ghost,
                                  subgrid=subgrid)
    return hydro_rhs_pallas(u_slots, h=h, gamma=gamma, ghost=ghost,
                            subgrid=subgrid, layout=layout)


@partial(jax.jit, static_argnames=("use_pallas",))
def grouped_gemm(x, w, group_len, use_pallas=True):
    if not use_pallas:
        return _ref.grouped_gemm_ref(x, w, group_len)
    return _gg_pallas(x, w, group_len)


@partial(jax.jit, static_argnames=("use_pallas",))
def decode_attention(q, k_cache, v_cache, cache_len, use_pallas=True):
    if not use_pallas:
        return _ref.decode_attention_ref(q, k_cache, v_cache, cache_len)
    return _decode_pallas(q, k_cache, v_cache, cache_len)
