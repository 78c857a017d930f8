"""Plain whole-grid reference for the uniform Sedov blast wave.

The same mathematics as the program's task body, written without tasks,
buckets, strategies or sub-grids: PPM reconstruction at 26 surface points
per cell along the 13 direction pairs (Colella & Woodward 1984),
central-upwind fluxes (Kurganov, Noelle & Petrova 2001) at 3x3 Simpson
quadrature points per face, and Shu-Osher TVD-RK3 over the assembled
``(F, N, N, N)`` grid with outflow (edge-copy) boundaries and a Courant
time step.  It imports nothing of the program.

The grid is evaluated in x-slabs of ``SLAB`` cells, each padded by the
ghost width, so that the reference fits beside the state at every size.
Every function takes the state in the dtype it should compute in: the
benchmark's control runs the same code in bfloat16.
"""
from __future__ import annotations

import json
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

RHO_FLOOR = 1e-10
P_FLOOR = 1e-12
SLAB = 32

# the 13 canonical direction pairs: faces, then edges, then vertices
_DIRS = [(dx, dy, dz) for dx in (-1, 0, 1) for dy in (-1, 0, 1)
         for dz in (-1, 0, 1) if (dx, dy, dz) != (0, 0, 0)]


def _canonical(d):
    for c in d:
        if c:
            return c > 0
    return False


PAIRS = sorted((d for d in _DIRS if _canonical(d)),
               key=lambda d: (sum(c * c for c in d), d))
_PAIR = {d: i for i, d in enumerate(PAIRS)}


def _canon(d):
    """Canonical pair of ``d`` and whether ``d`` is its + member."""
    for c in d:
        if c:
            return (d, True) if c > 0 else (tuple(-x for x in d), False)
    raise ValueError(d)


def _shift(u, d, k):
    """u(i + k*d) over the last three axes (wrap only touches ghosts)."""
    if k == 0:
        return u
    return jnp.roll(u, (-k * d[0], -k * d[1], -k * d[2]), axis=(-3, -2, -1))


def ppm(u, d):
    """Limited-parabola surface values of every cell toward -d and +d."""
    um2, um1 = _shift(u, d, -2), _shift(u, d, -1)
    up1, up2 = _shift(u, d, 1), _shift(u, d, 2)
    ul = (7.0 / 12.0) * (um1 + u) - (1.0 / 12.0) * (um2 + up1)
    ur = (7.0 / 12.0) * (u + up1) - (1.0 / 12.0) * (um1 + up2)
    extremum = (ur - u) * (u - ul) <= 0.0
    du = ur - ul
    u6 = 6.0 * (u - 0.5 * (ul + ur))
    ul_new = jnp.where(du * u6 > du * du, 3.0 * u - 2.0 * ur, ul)
    ur_new = jnp.where(-(du * du) > du * u6, 3.0 * u - 2.0 * ul, ur)
    return jnp.where(extremum, u, ul_new), jnp.where(extremum, u, ur_new)


def primitives(u, gamma):
    rho = jnp.maximum(u[0], RHO_FLOOR)
    vx, vy, vz = u[1] / rho, u[2] / rho, u[3] / rho
    ke = 0.5 * rho * (vx * vx + vy * vy + vz * vz)
    p = jnp.maximum((gamma - 1.0) * (u[4] - ke), P_FLOOR)
    return rho, vx, vy, vz, p


def point_flux(u, axis, gamma):
    rho, vx, vy, vz, p = primitives(u, gamma)
    v = (vx, vy, vz)[axis]
    f = jnp.stack([rho * v, u[1] * v, u[2] * v, u[3] * v, (u[4] + p) * v])
    return f.at[1 + axis].add(p)


def central_upwind(uL, uR, axis, gamma):
    rhoL, vxL, vyL, vzL, pL = primitives(uL, gamma)
    rhoR, vxR, vyR, vzR, pR = primitives(uR, gamma)
    vL, vR = (vxL, vyL, vzL)[axis], (vxR, vyR, vzR)[axis]
    cL = jnp.sqrt(gamma * pL / rhoL)
    cR = jnp.sqrt(gamma * pR / rhoR)
    ap = jnp.maximum(jnp.maximum(vL + cL, vR + cR), 0.0)
    am = jnp.minimum(jnp.minimum(vL - cL, vR - cR), 0.0)
    fL, fR = point_flux(uL, axis, gamma), point_flux(uR, axis, gamma)
    span = ap - am
    inv = jnp.where(span > 1e-12, 1.0 / jnp.maximum(span, 1e-12), 0.0)
    flux = (ap * fL - am * fR) * inv + (ap * am) * inv * (uR - uL)
    return jnp.where(span > 1e-12, flux, 0.5 * (fL + fR))


_SIMPSON = {-1: 1.0 / 6.0, 0: 4.0 / 6.0, 1: 1.0 / 6.0}


def face_flux(recon, axis, gamma):
    """Simpson-integrated flux through the +axis face of every cell."""
    e = [0, 0, 0]
    e[axis] = 1
    dims = [i for i in range(3) if i != axis]
    total = None
    for t1 in (-1, 0, 1):
        for t2 in (-1, 0, 1):
            t = [0, 0, 0]
            t[dims[0]], t[dims[1]] = t1, t2
            cL, plusL = _canon(tuple(e[i] + t[i] for i in range(3)))
            cR, plusR = _canon(tuple(-e[i] + t[i] for i in range(3)))
            uL = recon[_PAIR[cL]][int(plusL)]
            uR = _shift(recon[_PAIR[cR]][int(plusR)], tuple(e), 1)
            f = _SIMPSON[t1] * _SIMPSON[t2] * central_upwind(uL, uR, axis,
                                                             gamma)
            total = f if total is None else total + f
    return total


def rhs_padded(up, h, gamma, ghost):
    """dU/dt over the interior of a ghost-padded block ``(F, X+2g, Y+2g,
    Z+2g)`` -> ``(F, X, Y, Z)``."""
    recon = [ppm(up, d) for d in PAIRS]
    g = ghost
    n = [s - 2 * g for s in up.shape[1:]]
    out = None
    for axis in range(3):
        fp = face_flux(recon, axis, gamma)
        lo, hi = [g, g, g], [g + n[0], g + n[1], g + n[2]]
        f_hi = fp[:, lo[0]:hi[0], lo[1]:hi[1], lo[2]:hi[2]]
        lo[axis] -= 1
        hi[axis] -= 1
        f_lo = fp[:, lo[0]:hi[0], lo[1]:hi[1], lo[2]:hi[2]]
        d = (f_hi - f_lo) / h
        out = -d if out is None else out - d
    return out


def rhs(u, hydro):
    """dU/dt over the whole grid, in x-slabs of ``SLAB`` cells."""
    g, n = hydro["ghost"], u.shape[-1]
    h = hydro["domain"] / n
    up = jnp.pad(u, [(0, 0)] + [(g, g)] * 3, mode="edge")
    w = min(SLAB, n)
    starts = jnp.arange(0, n, w)

    def slab(x0):
        block = jax.lax.dynamic_slice_in_dim(up, x0, w + 2 * g, axis=1)
        return rhs_padded(block, h, hydro["gamma"], g)

    out = jax.lax.map(slab, starts)                  # (n/w, F, w, n, n)
    return jnp.moveaxis(out, 0, 1).reshape(u.shape)


def courant_dt(u, hydro):
    rho, vx, vy, vz, p = primitives(u, hydro["gamma"])
    c = jnp.sqrt(hydro["gamma"] * p / rho)
    vmax = jnp.max(jnp.sqrt(vx * vx + vy * vy + vz * vz) + c)
    return hydro["cfl"] * (hydro["domain"] / u.shape[-1]) / vmax


def magnitude(u, hydro):
    """Each cell's own magnitude per field, the unit in which a gap between
    two states is read: |density| and |energy|, and for each momentum
    |momentum| plus density times sound speed, the momentum that a wave
    carries through a cell at rest (whose own momentum is 0)."""
    rho, _, _, _, p = primitives(u, hydro["gamma"])
    wave = rho * jnp.sqrt(hydro["gamma"] * p / rho)
    zero = jnp.zeros_like(wave)
    return jnp.abs(u) + jnp.stack([zero, wave, wave, wave, zero])


def make_step(config):
    """``u -> (dt, u_next)``: one TVD-RK3 step with its own Courant dt, in
    the dtype of ``u``.  The right-hand side is one jitted program called
    three times."""
    return _step_fn(json.dumps(config["hydro"], sort_keys=True))


@lru_cache(maxsize=None)
def _step_fn(hydro: str):
    hydro = json.loads(hydro)
    rhs_jit = jax.jit(partial(rhs, hydro=hydro))
    dt_jit = jax.jit(partial(courant_dt, hydro=hydro))

    def step(u):
        dt = dt_jit(u)
        u1 = u + dt * rhs_jit(u)
        u2 = 0.75 * u + 0.25 * (u1 + dt * rhs_jit(u1))
        return dt, (1.0 / 3.0) * u + (2.0 / 3.0) * (u2 + dt * rhs_jit(u2))
    return step


def _initial(key, hydro, noise):
    n = 2 ** hydro["levels"] * hydro["subgrid"]
    h = hydro["domain"] / n
    x = (jnp.arange(n) + 0.5) * h - 0.5 * hydro["domain"]
    X, Y, Z = jnp.meshgrid(x, x, x, indexing="ij")
    in_blast = jnp.sqrt(X * X + Y * Y + Z * Z) < 3.5 * h
    n_blast = jnp.maximum(jnp.sum(in_blast), 1)
    e_blast = hydro["blast_energy"] / (n_blast * h ** 3)
    energy = jnp.where(in_blast, e_blast, 1e-8 / (hydro["gamma"] - 1.0))
    rho = hydro["rho0"] * (1.0 + noise * jax.random.uniform(
        key, (n, n, n), minval=-1.0, maxval=1.0))
    zeros = jnp.zeros_like(rho)
    u = jnp.stack([rho, zeros, zeros, zeros, energy])
    return u.astype(hydro["dtype"])


def initial_state(config, seed: int):
    """Sedov-Taylor blast at rest (pressure 1e-8 outside a sphere of 3.5
    cells that holds ``blast_energy``), with density ``rho0`` times
    ``1 + density_noise * U(-1, 1)`` drawn per cell from ``seed``; made on
    the device in one jitted call.  Velocities are zero, so the energy
    does not depend on the density."""
    key = jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    return _initial_jit(json.dumps(config["hydro"], sort_keys=True),
                        config["density_noise"])(key)


@lru_cache(maxsize=None)
def _initial_jit(hydro: str, noise: float):
    return jax.jit(partial(_initial, hydro=json.loads(hydro), noise=noise))


def step_work(config, counts):
    """FLOPs and HBM bytes one RK3 step needs at least, from the frozen
    per-sub-grid counts: every stage reads each ghost-padded sub-grid once
    and writes its interior update once."""
    hydro = config["hydro"]
    s, g, f = hydro["subgrid"], hydro["ghost"], hydro["n_fields"]
    n_sub = (2 ** hydro["levels"]) ** 3
    stages = counts["stages_per_step"]
    itemsize = jnp.dtype(hydro["dtype"]).itemsize
    flops = stages * n_sub * counts["per_subgrid"][str(s)]["flops"]
    hbm = stages * n_sub * f * ((s + 2 * g) ** 3 + s ** 3) * itemsize
    return flops, hbm
