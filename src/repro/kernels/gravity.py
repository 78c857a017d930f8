"""Per-sub-grid gravity kernel (compact Poisson-relaxation body + Pallas twin).

Octo-Tiger aggregates TWO kernel families through the same runtime: the
hydro Reconstruct+Flux pair and the gravity (FMM) solver.  This module is
the gravity family for the repro: a compact per-sub-grid Poisson solve —
``n_iter`` Jacobi relaxation sweeps of ``laplace(phi) = 4 pi G rho`` on one
padded sub-grid with zero-Dirichlet values on the pad frame, followed by a
central-difference gradient — standing in for one FMM leaf interaction.
Like ``subgrid_rhs`` it is ONE fine-grained task body, sized for one core,
that every aggregation strategy re-granularizes; unlike the global FMM it
needs no cross-task coupling, which is exactly what makes it aggregable.

The cell width ``h`` is a *traced* per-task argument (matching
``repro.hydro.stepper.level_batched_body``'s convention), so one compiled
bucket serves every refinement level whose sub-grid shapes agree and the
body opens its own ``TaskSignature`` family — distinct from hydro's by
kernel id — when both are submitted to one ``AggregationExecutor``.

The Pallas twin (``gravity_pallas``, slot_grid layout) runs the same block
math with the aggregated-task axis as the kernel grid: Mosaic-compiled on
TPU, and validated bit-exact against the jnp oracle in interpret mode
elsewhere (tests/test_gravity.py).
"""
from __future__ import annotations

import functools
from functools import lru_cache

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import pallas_call


def _interior_mask(p: int):
    """(p, p, p) bool: True off the one-cell Dirichlet frame (2D+ iota only,
    Pallas-safe)."""
    ii = jax.lax.broadcasted_iota(jnp.int32, (p, p, p), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (p, p, p), 1)
    kk = jax.lax.broadcasted_iota(jnp.int32, (p, p, p), 2)

    def inner(x):
        return (x > 0) & (x < p - 1)

    return inner(ii) & inner(jj) & inner(kk)


def _gravity_block(rho, h, *, ghost: int, subgrid: int, g_const: float,
                   n_iter: int):
    """Shared block math: (P, P, P) density + scalar h -> (4, S, S, S).

    Output fields are [phi, gx, gy, gz] over the interior, with
    ``g = -grad(phi)`` by central differences.  ``n_iter`` is static (the
    sweep loop unrolls); ``h`` may be traced.
    """
    p = rho.shape[-1]
    mask = _interior_mask(p)
    rhs = (4.0 * jnp.pi * g_const) * rho * (h * h)
    phi = jnp.zeros_like(rho)
    for _ in range(n_iter):
        nb = (jnp.roll(phi, 1, -3) + jnp.roll(phi, -1, -3)
              + jnp.roll(phi, 1, -2) + jnp.roll(phi, -1, -2)
              + jnp.roll(phi, 1, -1) + jnp.roll(phi, -1, -1))
        phi = jnp.where(mask, (nb - rhs) / 6.0, 0.0)
    inv2h = 0.5 / h
    gx = (jnp.roll(phi, 1, -3) - jnp.roll(phi, -1, -3)) * inv2h
    gy = (jnp.roll(phi, 1, -2) - jnp.roll(phi, -1, -2)) * inv2h
    gz = (jnp.roll(phi, 1, -1) - jnp.roll(phi, -1, -1)) * inv2h
    g, s = ghost, subgrid
    sl = (slice(g, g + s),) * 3
    return jnp.stack([phi[sl], gx[sl], gy[sl], gz[sl]])


def subgrid_gravity(u_padded, h, *, ghost: int, subgrid: int,
                    g_const: float = 1.0, n_iter: int = 8):
    """One gravity task: (F, P, P, P) conserved sub-grid -> (4, S, S, S)
    [phi, gx, gy, gz].  Only the density field feeds the solve, but the
    body takes the full padded sub-grid so hydro and gravity tasks can
    reference the SAME ghost-exchanged parent array."""
    return _gravity_block(u_padded[0], h, ghost=ghost, subgrid=subgrid,
                          g_const=g_const, n_iter=n_iter)


def gravity_source_update(u, dudt, pg, scale=None):
    """Add the gravity source to a hydro update: momentum gains
    ``rho * g`` and energy gains ``S . g`` — the coupling Octo-Tiger
    applies between its hydro and FMM solver families.  Pointwise, so it
    serves assembled global grids and per-slot interiors alike.

    ``scale=None`` adds the raw source (the rhs combine — kept
    multiplication-free so that path's bits never move); a traced scalar
    scales every term, which is how the epilogue-fused stage combine
    folds its ``c1 * dt`` factor in (DESIGN.md §10):
    ``c0*u0 + c1*(v + dt*(dudt + src)) == stage(dudt) + c1*dt*src``.
    """
    rho = u[0]
    gx, gy, gz = pg[1], pg[2], pg[3]
    terms = (rho * gx, rho * gy, rho * gz,
             u[1] * gx + u[2] * gy + u[3] * gz)
    if scale is not None:
        terms = tuple(scale * t for t in terms)
    return (dudt.at[1].add(terms[0])
                .at[2].add(terms[1])
                .at[3].add(terms[2])
                .at[4].add(terms[3]))


@lru_cache(maxsize=None)
def gravity_batched_body(ghost: int, subgrid: int, g_const: float = 1.0,
                         n_iter: int = 8):
    """The aggregation-region body: ``(k, F, P, P, P), (k,) -> (k, 4, S, S,
    S)`` with per-task traced h.  Cached so every runner / reference
    sharing the parameters gets the SAME callable (and compiled programs),
    mirroring ``repro.hydro.stepper.level_batched_body``; its jitted twin
    compiles to ``jit_gravity``."""
    def gravity(u_padded, h):
        return subgrid_gravity(u_padded, h, ghost=ghost, subgrid=subgrid,
                               g_const=g_const, n_iter=n_iter)
    return jax.vmap(gravity)


@lru_cache(maxsize=None)
def gravity_batched_jit(ghost: int, subgrid: int, g_const: float = 1.0,
                        n_iter: int = 8):
    """Jitted twin of :func:`gravity_batched_body` (per-family fused launch)."""
    return jax.jit(gravity_batched_body(ghost, subgrid, g_const, n_iter))


# ---------------------------------------------------------------------------
# Pallas kernel (slot_grid layout, per-slot traced h)
# ---------------------------------------------------------------------------

# Scoped-VMEM limit: one padded sub-grid's relaxation sweeps need under
# 1 MB at 8^3 and 1.14 MB at 16^3 (compiled for v5e), far inside it.
GRAVITY_VMEM_BYTES = 16 << 20


def _kernel_gravity_slot_grid_h(h_ref, u_ref, out_ref, *, ghost, subgrid,
                                g_const, n_iter):
    u = u_ref[0]                                  # (F, P, P, P)
    h = h_ref[pl.program_id(0)]
    out_ref[0] = _gravity_block(u[0], h, ghost=ghost, subgrid=subgrid,
                                g_const=g_const, n_iter=n_iter)


def gravity_pallas(u_slots: jax.Array, h_slots: jax.Array, *, ghost: int,
                   subgrid: int, g_const: float = 1.0,
                   n_iter: int = 8) -> jax.Array:
    """Aggregated gravity kernel: (slots, F, P, P, P) -> (slots, 4, S, S, S).

    slot_grid layout (one task per grid step, as in ``hydro_rhs_pallas``);
    per-slot cell widths are scalar-prefetched into SMEM.
    """
    n, f, p = u_slots.shape[0], u_slots.shape[1], u_slots.shape[2]
    s = subgrid
    return pallas_call(
        functools.partial(_kernel_gravity_slot_grid_h, ghost=ghost,
                          subgrid=subgrid, g_const=g_const, n_iter=n_iter),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n,),
            in_specs=[pl.BlockSpec((1, f, p, p, p),
                                   lambda i, h_ref: (i, 0, 0, 0, 0))],
            out_specs=pl.BlockSpec((1, 4, s, s, s),
                                   lambda i, h_ref: (i, 0, 0, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((n, 4, s, s, s), u_slots.dtype),
        vmem_limit_bytes=GRAVITY_VMEM_BYTES,
    )(jnp.reshape(h_slots, (n,)), u_slots)


def pallas_gravity_batched_body_h(ghost: int, subgrid: int,
                                  g_const: float = 1.0, n_iter: int = 8):
    """Pallas-backed drop-in for :func:`gravity_batched_body` (same
    ``(u_slots, h_slots)`` calling convention) — registers as the gravity
    family's aggregation-region body on real TPU."""
    def batched(u_slots, h_slots):
        return gravity_pallas(u_slots, h_slots, ghost=ghost, subgrid=subgrid,
                              g_const=g_const, n_iter=n_iter)
    return batched
