"""TVD-RK3 time stepping over the sub-grid decomposition.

One time-step = three hydro-solver iterations (paper §VI-A: "each time-step
including three iterations"), each iteration being a ghost exchange followed
by per-sub-grid Reconstruct + Flux (the paper's two dominant kernels) and the
conserved-variable update.  ``courant_dt`` implements the Courant condition
(paper §IV-B).

``subgrid_rhs`` is THE task body: one fine-grained unit of work, sized for
one CPU core in Octo-Tiger's original design.  Every aggregation strategy in
``repro.core`` re-granularizes launches of this body (or of its Pallas
twin in ``repro.kernels``).
"""
from __future__ import annotations

from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from repro.configs.base import AMRHydroConfig, HydroConfig
from repro.hydro.euler import max_signal_speed
from repro.hydro.flux import flux_divergence
from repro.hydro.ppm import ppm_reconstruct_all
from repro.hydro.state import (
    AMRState, HydroState, assemble_global, extract_subgrids,
    extract_subgrids_multilevel, sync_coarse,
)


def subgrid_rhs(u_padded, h, gamma: float, ghost: int, subgrid: int):
    """One task: PPM reconstruct + central-upwind flux on one padded sub-grid.

    u_padded: (F, P, P, P) -> dU/dt over the interior (F, S, S, S).
    ``h`` may be a python float (baked at trace time) or a traced scalar —
    the multi-level runners pass it as a per-task argument so ONE compiled
    bucket serves every refinement level whose sub-grid shapes agree.
    """
    recon = ppm_reconstruct_all(u_padded)
    return flux_divergence(recon, h, gamma, ghost, subgrid)


@lru_cache(maxsize=None)
def level_batched_body(gamma: float, ghost: int, subgrid: int):
    """The shape-polymorphic aggregation-region body for one sub-grid size:
    ``(k, F, P, P, P), (k,) -> (k, F, S, S, S)`` with per-task traced h.
    Cached so every runner / reference sharing (gamma, ghost, subgrid) gets
    the SAME callable — and therefore the same compiled programs.  Named
    after the AMR family it serves, so its jitted twin compiles to
    ``jit_hydro_rhs_s<subgrid>``."""
    def body(u_padded, h):
        return subgrid_rhs(u_padded, h, gamma=gamma, ghost=ghost,
                           subgrid=subgrid)
    body.__name__ = f"hydro_rhs_s{subgrid}"
    return jax.vmap(body)


@lru_cache(maxsize=None)
def level_batched_jit(gamma: float, ghost: int, subgrid: int):
    """Jitted twin of :func:`level_batched_body` (per-level fused launch)."""
    return jax.jit(level_batched_body(gamma, ghost, subgrid))


def rk_stage_epilogue(dudt, v_int, u0_int, c0, c1, dt):
    """The per-slot RK-stage epilogue (DESIGN.md §9): one Shu-Osher stage
    update over a task's interior, ``out = c0*u0 + c1*(v + dt*dudt)``
    (stage 1 is ``c0=0, c1=1``; stages 2/3 are ``0.75,0.25`` / ``1/3,2/3``).

    Declared on :class:`~repro.core.scenario.KernelFamily` so the epilogue
    traces *into* the bucketed aggregation program: gather -> Reconstruct+
    Flux -> stage axpy compile to ONE XLA program per bucket, and a time
    step becomes three launches instead of three launches plus global
    combine traffic.  Coefficients arrive as per-task traced scalars, so a
    single compiled bucket serves all three stages.  Every hydro-family
    scenario shares THIS epilogue — uniform Sedov, the per-level AMR
    twins (traced ``h`` rides through the fused body untouched) and the
    gravity scenario's hydro family (DESIGN.md §10).
    """
    return c0 * u0_int + c1 * (v_int + dt * dudt)


def stage_coeff_vectors(cache: dict, dt, c0: float, c1: float, n: int,
                        dtype):
    """Per-task ``(c0, c1, dt)`` coefficient vectors for one epilogue-fused
    RK stage, cached per ``(c0, c1, n)`` and rebuilt only when the ``dt``
    object changes: fixed-dt drivers re-hit three cached broadcasts per
    stage instead of dispatching three ``jnp.full``.  Shared by every
    scenario implementing ``stage_populations`` (the caller owns the
    cache dict, one per scenario instance)."""
    key = (c0, c1, n)
    hit = cache.get(key)
    if hit is None or hit[0] is not dt:
        hit = (dt, tuple(jnp.full((n,), c, dtype) for c in (c0, c1, dt)))
        cache[key] = hit
    return hit[1]


def _rhs_global(u, cfg: HydroConfig, h: float, bc: str):
    subs = extract_subgrids(u, cfg.subgrid, cfg.ghost, bc)
    body = partial(subgrid_rhs, h=h, gamma=cfg.gamma,
                   ghost=cfg.ghost, subgrid=cfg.subgrid)
    dudt = jax.vmap(body)(subs)
    return assemble_global(dudt, cfg.subgrid)


def _rk3_body(u, dt, cfg: HydroConfig, bc: str):
    h = cfg.domain / u.shape[-1]
    l0 = _rhs_global(u, cfg, h, bc)
    u1 = u + dt * l0
    l1 = _rhs_global(u1, cfg, h, bc)
    u2 = 0.75 * u + 0.25 * (u1 + dt * l1)
    l2 = _rhs_global(u2, cfg, h, bc)
    return (1.0 / 3.0) * u + (2.0 / 3.0) * (u2 + dt * l2)


@partial(jax.jit, static_argnames=("cfg", "bc"))
def rk3_step(u, dt, cfg: HydroConfig, bc: str = "outflow"):
    """Shu-Osher TVD-RK3: three iterations of the hydro solver."""
    return _rk3_body(u, dt, cfg, bc)


@partial(jax.jit, static_argnames=("cfg", "n_steps", "bc"),
         donate_argnums=(0,))
def rk3_trajectory(u, dt, cfg: HydroConfig, n_steps: int,
                   bc: str = "outflow"):
    """``n_steps`` RK3 steps as ONE ``lax.scan`` program (fixed dt).

    The whole trajectory dispatches once; the state buffer is donated so
    XLA aliases the scan carry in place.  NOTE: donation invalidates the
    caller's ``u`` — pass a copy if the input must survive.  This is the
    fused-strategy upper bound extended over time (Table III's last row);
    ``run`` keeps the per-step loop because it recomputes the Courant dt
    between steps.
    """
    def body(v, _):
        return _rk3_body(v, dt, cfg, bc), None

    out, _ = jax.lax.scan(body, u, None, length=n_steps)
    return out


@partial(jax.jit, static_argnames=("cfg",))
def courant_dt(u, cfg: HydroConfig):
    h = cfg.domain / u.shape[-1]
    return cfg.cfl * h / max_signal_speed(u, cfg.gamma)


@jax.jit
def total_conserved(u, h):
    """(mass, Sx, Sy, Sz, E) integrals — conservation invariants."""
    return jnp.sum(u, axis=(1, 2, 3)) * h ** 3


def run(state: HydroState, cfg: HydroConfig, n_steps: int,
        bc: str = "outflow") -> HydroState:
    u, t = state.u, state.t
    for k in range(n_steps):
        dt = courant_dt(u, cfg)
        u = rk3_step(u, dt, cfg, bc)
        t = t + float(dt)
    return HydroState(u=u, t=t, step=state.step + n_steps)


# ---------------------------------------------------------------------------
# Two-level AMR stepping
# ---------------------------------------------------------------------------

def amr_rk3_step(rhs_fn, uc, uf, dt, cfg: AMRHydroConfig):
    """TVD-RK3 over both levels in lockstep (shared dt).

    ``rhs_fn(uc, uf) -> (duc, duf)`` is a strategy runner's rhs or the
    reference below; the combine arithmetic here is the single shared code
    path, so runner-vs-reference equivalence reduces to rhs equivalence.
    The covered coarse cells are re-synced from the fine solution at the
    end of the step.
    """
    dc0, df0 = rhs_fn(uc, uf)
    uc1, uf1 = uc + dt * dc0, uf + dt * df0
    dc1, df1 = rhs_fn(uc1, uf1)
    uc2 = 0.75 * uc + 0.25 * (uc1 + dt * dc1)
    uf2 = 0.75 * uf + 0.25 * (uf1 + dt * df1)
    dc2, df2 = rhs_fn(uc2, uf2)
    uc_new = (1.0 / 3.0) * uc + (2.0 / 3.0) * (uc2 + dt * dc2)
    uf_new = (1.0 / 3.0) * uf + (2.0 / 3.0) * (uf2 + dt * df2)
    return sync_coarse(uc_new, uf_new, cfg), uf_new


def amr_reference_rhs(uc, uf, cfg: AMRHydroConfig, bc: str = "outflow"):
    """Per-level FUSED reference: each level's whole task batch as one
    vmapped launch with per-task traced h.  The equivalence oracle every
    aggregation strategy must match bit-identically."""
    subs_c, subs_f = extract_subgrids_multilevel(uc, uf, cfg, bc)
    dtype = subs_c.dtype
    hc = jnp.full((subs_c.shape[0],), cfg.h_coarse, dtype)
    hf = jnp.full((subs_f.shape[0],), cfg.h_fine, dtype)
    duc = level_batched_jit(cfg.gamma, cfg.ghost, cfg.coarse_subgrid)(
        subs_c, hc)
    duf = level_batched_jit(cfg.gamma, cfg.ghost, cfg.fine_subgrid)(
        subs_f, hf)
    return (assemble_global(duc, cfg.coarse_subgrid),
            assemble_global(duf, cfg.fine_subgrid))


def amr_reference_step(uc, uf, dt, cfg: AMRHydroConfig,
                       bc: str = "outflow"):
    """One RK3 step of the per-level fused reference."""
    return amr_rk3_step(lambda a, b: amr_reference_rhs(a, b, cfg, bc),
                        uc, uf, dt, cfg)


@partial(jax.jit, static_argnames=("cfg",))
def amr_courant_dt(uc, uf, cfg: AMRHydroConfig):
    """Shared two-level Courant dt (the fine level is the binding one)."""
    return cfg.cfl * jnp.minimum(
        cfg.h_coarse / max_signal_speed(uc, cfg.gamma),
        cfg.h_fine / max_signal_speed(uf, cfg.gamma))


def amr_run(state: AMRState, cfg: AMRHydroConfig, n_steps: int,
            bc: str = "outflow") -> AMRState:
    uc, uf, t = state.uc, state.uf, state.t
    for _ in range(n_steps):
        dt = amr_courant_dt(uc, uf, cfg)
        uc, uf = amr_reference_step(uc, uf, dt, cfg, bc)
        t = t + float(dt)
    return AMRState(uc=uc, uf=uf, t=t, step=state.step + n_steps)


def shock_radius(u, cfg: HydroConfig):
    """Radius of the density peak — the Sedov shock front location."""
    n = u.shape[-1]
    h = cfg.domain / n
    x = (jnp.arange(n) + 0.5) * h - 0.5 * cfg.domain
    X, Y, Z = jnp.meshgrid(x, x, x, indexing="ij")
    r = jnp.sqrt(X * X + Y * Y + Z * Z)
    rho = u[0]
    # mass-weighted radius of the over-dense shell
    w = jnp.maximum(rho - cfg.rho0, 0.0)
    return jnp.sum(w * r) / jnp.maximum(jnp.sum(w), 1e-30)
