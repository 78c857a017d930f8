"""Aggregated hydro RHS Pallas kernel (Reconstruct + Flux, fused).

The paper's two dominant GPU kernels operate on one sub-grid each and write
the 26-direction reconstruction to device memory between them.  The
TPU-native adaptation fuses them: reconstruction values are recomputed
per-quadrature-entry inside VMEM instead of being staged through HBM.

Napkin math (8^3 sub-grid, f32): the unfused pair moves
``26*5*14^3*4 B = 1.43 MB`` of reconstruction data per sub-grid through HBM
twice (write + read); the fused kernel moves only the ``55 KB`` input and
``10 KB`` output — a ~50x cut in HBM traffic for ~2x recompute of the cheap
VPU stencil math.  On a 819 GB/s part this turns a memory-bound kernel pair
into a compute-bound single kernel.

Two block layouts are provided:

* ``slot_grid``  — grid iterates aggregated tasks; block = one padded
  sub-grid ``(1, F, P, P, P)``.  This is the direct port of the paper's GPU
  kernel (one block of work per task).
* ``slot_lane``  — the aggregated-task axis is the *minor (lane)* dimension:
  block ``(F, P, P, P, T)`` with T tasks vectorized across the 128 VPU
  lanes.  Aggregation does not just fill the device with blocks, it fills
  the vector unit — the TPU-native reading of "turn fine-grained tasks into
  one larger kernel".  (P=14 is lane-hostile: 14 pads to 128 lanes, wasting
  9x; slot-lane takes a bucket of up to 128 tasks whole and pads a larger
  one to 128-lane tiles.)

Compiled by Mosaic on TPU and validated in interpret mode elsewhere
(``repro.kernels.backend``) against ``ref.py``, the pure-jnp oracle used by
the production XLA path.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.hydro.euler import N_FIELDS
from repro.hydro.flux import FACE_QUAD
from repro.hydro.ppm import DIR_PAIRS
from repro.kernels.backend import pallas_call

_AXIS_VECS = [(1, 0, 0), (0, 1, 0), (0, 0, 1)]

# The stencil arithmetic below binds lax primitives directly.  A jnp call
# (an operator on a tracer included) is a nested ``jit`` that costs far
# more to trace than the primitive it binds, and one trace of the kernel
# makes ~7,000 of them.  Each function emits the primitives its jnp
# spelling would, in the same order, so the kernel computes the same bits.


def _roll(u, shift: int, axis: int):
    """``jnp.roll(u, shift, axis)`` for a static, non-zero shift."""
    ax, n = axis % u.ndim, u.shape[axis]
    i = -shift % n
    return lax.concatenate([lax.slice_in_dim(u, i, n, axis=ax),
                            lax.slice_in_dim(u, 0, i, axis=ax)], ax)


def _shift(u, d: Tuple[int, int, int], k: int, axes: Tuple[int, int, int]):
    """u(i + k*d) via one roll per spatial axis the shift moves along (a
    zero shift would lower to a zero-size slice, which Mosaic rejects)."""
    for dk, ax in zip(d, axes):
        if k * dk:
            u = _roll(u, -k * dk, ax)
    return u


def _ppm_limited(at, side: int):
    """CW84 limited-parabola surface value toward -d (side=0) or +d
    (side=1), where ``at(k)`` gives u(i + k*d)."""
    add, sub, mul = lax.add, lax.sub, lax.mul
    u = at(0)
    um2 = at(-2)
    um1 = at(-1)
    up1 = at(1)
    up2 = at(2)
    ul = sub(mul(7.0 / 12.0, add(um1, u)), mul(1.0 / 12.0, add(um2, up1)))
    ur = sub(mul(7.0 / 12.0, add(u, up1)), mul(1.0 / 12.0, add(um1, up2)))
    extremum = lax.le(mul(sub(ur, u), sub(u, ul)), 0.0)
    du = sub(ur, ul)
    u6 = mul(6.0, sub(u, mul(0.5, add(ul, ur))))
    ul_lim = lax.select(lax.gt(mul(du, u6), mul(du, du)),
                        sub(mul(3.0, u), mul(2.0, ur)), ul)
    ur_lim = lax.select(lax.gt(lax.neg(mul(du, du)), mul(du, u6)),
                        sub(mul(3.0, u), mul(2.0, ul)), ur)
    ul = lax.select(extremum, u, ul_lim)
    ur = lax.select(extremum, u, ur_lim)
    return ur if side else ul


def _ppm_side(u, d, side: int, axes):
    """:func:`_ppm_limited` on a whole block, shifting by rolls."""
    return _ppm_limited(lambda k: _shift(u, d, k, axes), side)


def _field(u, k: int):
    """``u[k]``: field ``k`` of a block with the field axis leading."""
    return lax.index_in_dim(u, k, keepdims=False)


def _lead(x):
    """``x`` with a unit field axis, to broadcast against (F, ...)."""
    return lax.expand_dims(x, (0,))


def _prim(u, gamma):
    """u: (F, ...) -> rho, vx, vy, vz, p (field axis leading)."""
    add, sub, mul, div = lax.add, lax.sub, lax.mul, lax.div
    rho = lax.max(_field(u, 0), 1e-10)
    vx, vy, vz = (div(_field(u, 1), rho), div(_field(u, 2), rho),
                  div(_field(u, 3), rho))
    ke = mul(mul(0.5, rho), add(add(mul(vx, vx), mul(vy, vy)), mul(vz, vz)))
    p = lax.max(mul(gamma - 1.0, sub(_field(u, 4), ke)), 1e-12)
    return rho, vx, vy, vz, p


def _phys_flux(u, axis, gamma):
    mul = lax.mul
    rho, vx, vy, vz, p = _prim(u, gamma)
    v = (vx, vy, vz)[axis]
    f = [mul(rho, v), mul(_field(u, 1), v), mul(_field(u, 2), v),
         mul(_field(u, 3), v), mul(lax.add(_field(u, 4), p), v)]
    f[1 + axis] = lax.add(f[1 + axis], p)
    return lax.concatenate([_lead(x) for x in f], 0)


def _central_upwind(uL, uR, axis, gamma):
    add, sub, mul, div = lax.add, lax.sub, lax.mul, lax.div
    rhoL, vxL, vyL, vzL, pL = _prim(uL, gamma)
    rhoR, vxR, vyR, vzR, pR = _prim(uR, gamma)
    vL = (vxL, vyL, vzL)[axis]
    vR = (vxR, vyR, vzR)[axis]
    cL = lax.sqrt(div(mul(gamma, pL), rhoL))
    cR = lax.sqrt(div(mul(gamma, pR), rhoR))
    ap = lax.max(lax.max(add(vL, cL), add(vR, cR)), 0.0)
    am = lax.min(lax.min(sub(vL, cL), sub(vR, cR)), 0.0)
    fL = _phys_flux(uL, axis, gamma)
    fR = _phys_flux(uR, axis, gamma)
    span = sub(ap, am)
    inv = lax.select(lax.gt(span, 1e-12), div(1.0, lax.max(span, 1e-12)),
                     lax.full_like(span, 0.0))
    flux = mul(sub(mul(_lead(ap), fL), mul(_lead(am), fR)), _lead(inv))
    jump = mul(mul(ap, am), inv)
    du = sub(uR, uL)
    flux = add(flux, mul(_lead(jump), du))
    wide = lax.gt(span, 1e-12)
    mean = mul(0.5, add(fL, fR))
    return lax.select(lax.broadcast_in_dim(wide, flux.shape,
                                           tuple(range(1, flux.ndim))),
                      flux, mean)


def _rhs_field_block(u, h, gamma: float, ghost: int, subgrid: int,
                     axes: Tuple[int, int, int]):
    """Fused Reconstruct+Flux on one block with field axis 0.

    u: (F, P, P, P[, T]); `axes` are the three spatial axes.
    Returns (F, S, S, S[, T]).
    """
    g, s = ghost, subgrid
    acc = None
    for axis in range(3):
        e = _AXIS_VECS[axis]
        face = None
        for (w, pL, sL, pR, sR) in FACE_QUAD[axis]:
            uL = _ppm_side(u, DIR_PAIRS[pL], sL, axes)
            uR = _shift(_ppm_side(u, DIR_PAIRS[pR], sR, axes), e, 1, axes)
            f = lax.mul(w, _central_upwind(uL, uR, axis, gamma))
            face = f if face is None else lax.add(face, f)
        # divergence over the interior
        def _slice(arr, lo):
            idx = [slice(None)] * arr.ndim
            for dim, ax in enumerate(axes):
                idx[ax] = slice(lo[dim], lo[dim] + s)
            return arr[tuple(idx)]
        hi_lo = [g, g, g]
        lo_lo = [g, g, g]
        lo_lo[axis] -= 1
        d = (_slice(face, hi_lo) - _slice(face, lo_lo)) / h
        acc = -d if acc is None else acc - d
    return acc


# -- x-plane form (slot_lane) -----------------------------------------------
# The same arithmetic, one x-plane (F, P, P, T) at a time: a shift along x
# picks another plane, shifts along y and z roll the plane.  Interior cells
# never reach a wrapped value in either form, so both give the same bits.

_PLANE_AXES = (-3, -2)                    # y, z of an x-plane (F, P, P, T)


def _plane_at(load, i: int, d, k: int):
    """u(i + k*d) restricted to x-plane ``i``; ``load(j)`` reads plane j.
    The x component picks the plane, so ``_shift`` only rolls y and z."""
    return _shift(load(lax.add(i, k * d[0])), (0, d[1], d[2]), k,
                  (0,) + _PLANE_AXES)


def _face_plane(load, i, axis: int, gamma: float):
    """Quadrature-summed flux through the ``axis`` faces of x-plane ``i``
    (for axis 0, the face between planes i and i+1)."""
    face = None
    for (w, pL, sL, pR, sR) in FACE_QUAD[axis]:
        dL, dR = DIR_PAIRS[pL], DIR_PAIRS[pR]
        uL = _ppm_limited(lambda k: _plane_at(load, i, dL, k), sL)
        if axis == 0:                     # the right state is on plane i+1
            uR = _ppm_limited(lambda k: _plane_at(load, i + 1, dR, k), sR)
        else:
            uR = _roll(_ppm_limited(lambda k: _plane_at(load, i, dR, k),
                                    sR), -1, _PLANE_AXES[axis - 1])
        f = lax.mul(w, _central_upwind(uL, uR, axis, gamma))
        face = f if face is None else lax.add(face, f)
    return face


def _rhs_planes(u_ref, out_ref, h, gamma: float, ghost: int, subgrid: int):
    """:func:`_rhs_field_block` for a lane-major block ``(F, P, P, P, T)``,
    looping over the interior x-planes.  A whole block of 128 tasks at once
    would need more VMEM for spills than the chip has, and unrolls into a
    kernel that takes minutes to compile."""
    g, s = ghost, subgrid
    inner = (slice(None), slice(g, g + s), slice(g, g + s))
    below = (None,
             (slice(None), slice(g - 1, g - 1 + s), slice(g, g + s)),
             (slice(None), slice(g, g + s), slice(g - 1, g - 1 + s)))

    def load(j):
        return u_ref[:, j]

    def plane(i, prev_face):
        face0 = _face_plane(load, i, 0, gamma)

        @pl.when(i >= g)
        def _():
            acc = -((face0[inner] - prev_face[inner]) / h)
            for axis in (1, 2):
                face = _face_plane(load, i, axis, gamma)
                acc = acc - (face[inner] - face[below[axis]]) / h
            out_ref[:, i - g] = acc
        return face0

    f, p, t = u_ref.shape[0], u_ref.shape[2], u_ref.shape[-1]
    jax.lax.fori_loop(g - 1, g + s, plane,
                      jnp.zeros((f, p, p, t), u_ref.dtype))


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

# Scoped-VMEM limits.  v5e has 128 MiB of VMEM and Mosaic's default scoped
# limit is 16 MiB.  Needs at 8^3 sub-grids, compiled for v5e: slot_grid
# 47 MB (static h) / 29.5 MB (traced h), slot_lane 9.3 MB for one 128-task
# tile and 17.8 MB for a 256-task bucket (double-buffered tiles).  A 16^3
# sub-grid needs 130.6 MB in slot_grid, more than the chip has.
VMEM_LIMIT_BYTES = 64 << 20
LANES = 128


def _kernel_slot_grid(u_ref, out_ref, *, h, gamma, ghost, subgrid):
    u = u_ref[0]                                  # (F, P, P, P)
    out_ref[0] = _rhs_field_block(u, h, gamma, ghost, subgrid,
                                  axes=(-3, -2, -1))


def _kernel_slot_grid_h(h_ref, u_ref, out_ref, *, gamma, ghost, subgrid):
    """Per-slot traced cell width: the widths are scalar-prefetched into
    SMEM and each grid step reads its own."""
    u = u_ref[0]                                  # (F, P, P, P)
    h = h_ref[pl.program_id(0)]
    out_ref[0] = _rhs_field_block(u, h, gamma, ghost, subgrid,
                                  axes=(-3, -2, -1))


def _kernel_slot_lane(u_ref, out_ref, *, h, gamma, ghost, subgrid):
    _rhs_planes(u_ref, out_ref, h, gamma, ghost, subgrid)


def _kernel_slot_lane_h(u_ref, h_ref, out_ref, *, gamma, ghost, subgrid):
    # h_ref holds (1, T) widths, which broadcast over the lanes
    _rhs_planes(u_ref, out_ref, h_ref[...], gamma, ghost, subgrid)


def hydro_rhs_pallas(u_slots: jax.Array, *, h: Optional[float] = None,
                     h_slots: Optional[jax.Array] = None, gamma: float,
                     ghost: int, subgrid: int,
                     layout: str = "slot_grid") -> jax.Array:
    """Aggregated RHS kernel: (slots, F, P, P, P) -> (slots, F, S, S, S).

    Cell width comes in one of two forms:

    * ``h``       — a python float baked into the program (uniform grid);
    * ``h_slots`` — a traced ``(slots,)`` array, one width per aggregated
      task (scalar-prefetched into SMEM for ``slot_grid``, one lane-row
      block per tile for ``slot_lane``).  This is the multi-level mode: one
      compiled kernel serves every refinement level whose sub-grid shapes
      agree (matching the XLA path's traced-h bodies).
    """
    if (h is None) == (h_slots is None):
        raise ValueError("pass exactly one of h / h_slots")
    call = rhs_program(layout, tuple(u_slots.shape), jnp.dtype(u_slots.dtype),
                       h, gamma, ghost, subgrid)
    return call(u_slots) if h_slots is None else call(u_slots, h_slots)


@functools.lru_cache(maxsize=None)
def rhs_program(layout: str, shape: Tuple[int, ...], dtype, h: Optional[float],
                gamma: float, ghost: int, subgrid: int):
    """The jitted kernel call for one static key; ``h=None`` takes a traced
    ``h_slots`` as its second argument.

    Every program that calls the kernel at one key (a region's gather,
    prefix and ring programs at one bucket, the three RK stages, the fused
    wrapper) calls this one ``jax.jit`` object, so JAX's trace cache traces
    the kernel once per process and key: one trace for the Mosaic branch
    and one for the interpret branch of ``backend.pallas_call``
    (``backend.kernel_traces`` counts them).
    """
    n, f, p = shape[0], shape[1], shape[2]
    s = subgrid
    kw = dict(gamma=gamma, ghost=ghost, subgrid=subgrid)
    name = f"hydro_rhs_{layout}"

    if layout == "slot_grid":
        out_shape = jax.ShapeDtypeStruct((n, f, s, s, s), dtype)
        if h is not None:
            call = pallas_call(
                functools.partial(_kernel_slot_grid, h=h, **kw),
                grid=(n,),
                in_specs=[pl.BlockSpec((1, f, p, p, p),
                                       lambda i: (i, 0, 0, 0, 0))],
                out_specs=pl.BlockSpec((1, f, s, s, s),
                                       lambda i: (i, 0, 0, 0, 0)),
                out_shape=out_shape, name=name,
                vmem_limit_bytes=VMEM_LIMIT_BYTES)
            return jax.jit(call)
        call = pallas_call(
            functools.partial(_kernel_slot_grid_h, **kw),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(n,),
                in_specs=[pl.BlockSpec((1, f, p, p, p),
                                       lambda i, h_ref: (i, 0, 0, 0, 0))],
                out_specs=pl.BlockSpec((1, f, s, s, s),
                                       lambda i, h_ref: (i, 0, 0, 0, 0))),
            out_shape=out_shape, name=name,
            vmem_limit_bytes=VMEM_LIMIT_BYTES)
        return jax.jit(lambda u, h_slots: call(jnp.reshape(h_slots, (n,)),
                                               u))

    if layout == "slot_lane":
        # tasks on the minor (lane) axis: (F, P, P, P, slots).  A bucket of
        # up to 128 tasks is one block (a block's minor dimension must be a
        # multiple of 128 or the whole array dimension); a larger bucket is
        # padded to whole 128-lane tiles, so that VMEM holds one tile of
        # 128 tasks whatever the bucket
        t = min(n, LANES)
        m = -(-n // t) * t

        def pad(x, fill):
            if m == n:
                return x
            return jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, m - n),),
                           constant_values=fill)

        def tasks(out):
            return (out if m == n else out[..., :n]).transpose(4, 0, 1, 2, 3)

        in_specs = [pl.BlockSpec((f, p, p, p, t), lambda i: (0, 0, 0, 0, i))]
        lane = dict(
            grid=(m // t,),
            out_specs=pl.BlockSpec((f, s, s, s, t),
                                   lambda i: (0, 0, 0, 0, i)),
            out_shape=jax.ShapeDtypeStruct((f, s, s, s, m), dtype),
            name=name, vmem_limit_bytes=VMEM_LIMIT_BYTES)
        if h is not None:
            call = pallas_call(
                functools.partial(_kernel_slot_lane, h=h, **kw),
                in_specs=in_specs, **lane)
            return jax.jit(lambda u: tasks(call(
                pad(u.transpose(1, 2, 3, 4, 0), 0.0))))
        call = pallas_call(
            functools.partial(_kernel_slot_lane_h, **kw),
            in_specs=in_specs + [pl.BlockSpec((1, t), lambda i: (0, i))],
            **lane)
        return jax.jit(lambda u, h_slots: tasks(call(
            pad(u.transpose(1, 2, 3, 4, 0), 0.0),
            pad(jnp.reshape(h_slots, (1, n)), 1.0))))

    raise ValueError(f"unknown layout {layout!r}")


# -- slot-ring integration --------------------------------------------------

def hydro_rhs_pallas_prefix(ring: jax.Array, start, bucket: int, *,
                            h: float, gamma: float, ghost: int, subgrid: int,
                            layout: str = "slot_grid") -> jax.Array:
    """Run the aggregated kernel on a slot-ring prefix, staging-free.

    ``ring`` is the AggregationExecutor's device-resident staging ring
    ``(capacity, F, P, P, P)``; the filled prefix ``[start, start+bucket)``
    is sliced *inside* the program (one fused op, no host copies) and fed to
    the Pallas kernel.  ``bucket`` is static — one compiled program per
    bucket size, matching the executor's bucket ladder.
    """
    u = jax.lax.dynamic_slice_in_dim(ring, start, bucket, axis=0)
    return hydro_rhs_pallas(u, h=h, gamma=gamma, ghost=ghost,
                            subgrid=subgrid, layout=layout)


def pallas_batched_body(cfg, h: float, layout: str = "slot_grid"):
    """Factory: a batched task body backed by the Pallas kernel, drop-in for
    ``UniformSedovScenario(batched_body=...)`` / ``AggregationExecutor`` —
    the path that runs the paper's GPU kernels through the slot-ring
    aggregation pipeline instead of the XLA oracle."""
    def batched(u_slots):
        return hydro_rhs_pallas(u_slots, h=h, gamma=cfg.gamma,
                                ghost=cfg.ghost, subgrid=cfg.subgrid,
                                layout=layout)
    return batched


def pallas_batched_body_h(gamma: float, ghost: int, subgrid: int,
                          layout: str = "slot_grid"):
    """Traced-h twin of :func:`pallas_batched_body`: signature
    ``(u_slots, h_slots) -> out_slots``, drop-in as a multi-level
    aggregation-region body (matches ``repro.hydro.stepper
    .level_batched_body``'s calling convention, Pallas-backed)."""
    def batched(u_slots, h_slots):
        return hydro_rhs_pallas(u_slots, h_slots=h_slots, gamma=gamma,
                                ghost=ghost, subgrid=subgrid, layout=layout)
    return batched


# -- split kernels (paper-faithful two-kernel structure) --------------------

def _kernel_reconstruct(u_ref, out_ref, *, axes=(-3, -2, -1)):
    """Reconstruct only: writes all 26 surface values (paper kernel 1)."""
    u = u_ref[0]
    outs = []
    for d in DIR_PAIRS:
        outs.append(jnp.stack([_ppm_side(u, d, 0, axes),
                               _ppm_side(u, d, 1, axes)]))
    out_ref[0] = jnp.stack(outs)


def hydro_reconstruct_pallas(u_slots: jax.Array):
    """(slots, F, P, P, P) -> (slots, 13, 2, F, P, P, P)."""
    n, f, p = u_slots.shape[0], u_slots.shape[1], u_slots.shape[2]
    npairs = len(DIR_PAIRS)
    return pallas_call(
        _kernel_reconstruct,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, f, p, p, p), lambda i: (i, 0, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, npairs, 2, f, p, p, p),
                               lambda i: (i, 0, 0, 0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, npairs, 2, f, p, p, p),
                                       u_slots.dtype),
    )(u_slots)


def _kernel_flux(recon_ref, out_ref, *, h, gamma, ghost, subgrid):
    """Flux only: consumes the staged reconstruction (paper kernel 2)."""
    recon = recon_ref[0]                          # (13, 2, F, P, P, P)
    g, s = ghost, subgrid
    axes = (-3, -2, -1)
    acc = None
    for axis in range(3):
        e = _AXIS_VECS[axis]
        face = None
        for (w, pL, sL, pR, sR) in FACE_QUAD[axis]:
            uL = recon[pL, sL]
            uR = _shift(recon[pR, sR], e, 1, axes)
            f = lax.mul(w, _central_upwind(uL, uR, axis, gamma))
            face = f if face is None else lax.add(face, f)
        hi = face[:, g:g + s, g:g + s, g:g + s]
        lo_idx = [slice(g, g + s)] * 3
        lo_idx[axis] = slice(g - 1, g - 1 + s)
        lo = face[(slice(None),) + tuple(lo_idx)]
        d = (hi - lo) / h
        acc = -d if acc is None else acc - d
    out_ref[0] = acc


def hydro_flux_pallas(recon: jax.Array, *, h: float, gamma: float,
                      ghost: int, subgrid: int):
    """(slots, 13, 2, F, P, P, P) -> (slots, F, S, S, S)."""
    n, npairs, _, f, p = recon.shape[:5]
    s = subgrid
    return pallas_call(
        functools.partial(_kernel_flux, h=h, gamma=gamma, ghost=ghost,
                          subgrid=subgrid),
        grid=(n,),
        in_specs=[pl.BlockSpec((1, npairs, 2, f, p, p, p),
                               lambda i: (i, 0, 0, 0, 0, 0, 0))],
        out_specs=pl.BlockSpec((1, f, s, s, s), lambda i: (i, 0, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n, f, s, s, s), recon.dtype),
    )(recon)
