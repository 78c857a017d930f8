"""The uniform hydro body: which one runs where, what it costs to trace,
and that it runs wherever the uniform scenario goes.

On a TPU the uniform scenario's default batched body is the ``slot_lane``
Pallas kernel at the sub-grid shapes where it is measured to fit and to
match the jnp body; everywhere else it is the vmapped jnp stencil.  The
kernel's stencil binds lax primitives (a jnp call is a nested ``jit``),
and its jitted call is memoised per static key, so the programs that hold
it at one shape share one trace of each branch (Mosaic and interpret).
"""
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import AggregationConfig, HydroConfig
from repro.configs.sedov import CONFIG
from repro.core import (
    AggregationExecutor, ShardedAggregationExecutor, StrategyRunner,
    TenantBatcher,
)
from repro.core.scenario import UniformSedovScenario, uniform_batched_body
from repro.core.trace import kernel_traces
from repro.distributed.api import subgrid_mesh
from repro.hydro.state import sedov_init
from repro.hydro.stepper import courant_dt
from repro.kernels.hydro_rhs import pallas_batched_body, rhs_program

K = importlib.import_module("repro.kernels.hydro_rhs")
H = 1.0 / 64
F32 = jnp.dtype("float32")


def _runs_pallas(body, cfg) -> bool:
    p = cfg.padded
    spec = jax.ShapeDtypeStruct((1, cfg.n_fields, p, p, p), jnp.float32)
    return "pallas_call" in str(jax.make_jaxpr(body)(spec))


@pytest.mark.parametrize("platform,subgrid,pallas", [
    ("tpu", 8, True),
    ("cpu", 8, False),
    ("tpu", 16, False),
])
def test_uniform_body_selection(platform, subgrid, pallas):
    cfg = dataclasses.replace(CONFIG, subgrid=subgrid)
    body = uniform_batched_body(cfg, H, platform)
    assert _runs_pallas(body, cfg) is pallas


def test_scenario_default_body_follows_this_host():
    on_tpu = jax.devices()[0].platform == "tpu"
    assert _runs_pallas(UniformSedovScenario(CONFIG).batched_body,
                        CONFIG) is on_tpu


def test_kernel_traced_once_per_branch_across_region_programs():
    """A region's gather, prefix and ring programs at one bucket share one
    trace of the kernel's Mosaic branch and one of its interpret branch."""
    cfg = dataclasses.replace(CONFIG, subgrid=4, levels=1)
    h = 0.0123                        # a key no other test traces
    ex = AggregationExecutor(pallas_batched_body(cfg, h, "slot_lane"),
                             AggregationConfig(max_aggregated=2))
    p = cfg.padded
    task = jnp.zeros((cfg.n_fields, p, p, p), jnp.float32)
    parents = jax.ShapeDtypeStruct((2,) + task.shape, task.dtype)
    region = ex._region_for("region", (task,))
    before = kernel_traces["hydro_rhs_slot_lane"]
    start = jax.ShapeDtypeStruct((), jnp.int32)
    region.program(2, "gather").trace(
        jax.ShapeDtypeStruct((2,), jnp.int32), parents)
    region.program(2, "prefix").trace(start, parents)
    region.program(2, "ring").trace(start, parents)
    assert kernel_traces["hydro_rhs_slot_lane"] - before == 2


def _ppm_limited_jnp(at, side):
    u, um2, um1, up1, up2 = at(0), at(-2), at(-1), at(1), at(2)
    ul = (7.0 / 12.0) * (um1 + u) - (1.0 / 12.0) * (um2 + up1)
    ur = (7.0 / 12.0) * (u + up1) - (1.0 / 12.0) * (um1 + up2)
    extremum = (ur - u) * (u - ul) <= 0.0
    du = ur - ul
    u6 = 6.0 * (u - 0.5 * (ul + ur))
    ul_lim = jnp.where(du * u6 > du * du, 3.0 * u - 2.0 * ur, ul)
    ur_lim = jnp.where(-(du * du) > du * u6, 3.0 * u - 2.0 * ul, ur)
    return jnp.where(extremum, u, ur_lim if side else ul_lim)


def _prim_jnp(u, gamma):
    rho = jnp.maximum(u[0], 1e-10)
    vx, vy, vz = u[1] / rho, u[2] / rho, u[3] / rho
    ke = 0.5 * rho * (vx * vx + vy * vy + vz * vz)
    return rho, vx, vy, vz, jnp.maximum((gamma - 1.0) * (u[4] - ke), 1e-12)


def _phys_flux_jnp(u, axis, gamma):
    rho, vx, vy, vz, p = _prim_jnp(u, gamma)
    v = (vx, vy, vz)[axis]
    f = [rho * v, u[1] * v, u[2] * v, u[3] * v, (u[4] + p) * v]
    f[1 + axis] = f[1 + axis] + p
    return jnp.stack(f)


def _central_upwind_jnp(uL, uR, axis, gamma):
    rhoL, *vL, pL = _prim_jnp(uL, gamma)
    rhoR, *vR, pR = _prim_jnp(uR, gamma)
    vL, vR = vL[axis], vR[axis]
    cL = jnp.sqrt(gamma * pL / rhoL)
    cR = jnp.sqrt(gamma * pR / rhoR)
    ap = jnp.maximum(jnp.maximum(vL + cL, vR + cR), 0.0)
    am = jnp.minimum(jnp.minimum(vL - cL, vR - cR), 0.0)
    fL, fR = _phys_flux_jnp(uL, axis, gamma), _phys_flux_jnp(uR, axis, gamma)
    span = ap - am
    inv = jnp.where(span > 1e-12, 1.0 / jnp.maximum(span, 1e-12), 0.0)
    flux = (ap * fL - am * fR) * inv + (ap * am) * inv * (uR - uL)
    return jnp.where(span > 1e-12, flux, 0.5 * (fL + fR))


@pytest.mark.parametrize("axis", [0, 1, 2])
def test_lax_stencil_equals_its_jnp_spelling(axis):
    """The kernel's lax stencil is the jnp arithmetic it replaced, bit for
    bit: PPM on both sides, the central-upwind flux, and the rolls."""
    key = jax.random.split(jax.random.PRNGKey(axis), 2)
    shape = (5, 10, 10, 16)
    uL = jax.random.uniform(key[0], shape, minval=-0.5, maxval=2.0)
    uR = jax.random.uniform(key[1], shape, minval=-0.5, maxval=2.0)
    d, axes = (1, -1, 1), (1, 2, 3)

    def at(k):
        return K._shift(uL, d, k, axes)

    def at_jnp(k):
        out = uL
        for dk, ax in zip(d, axes):
            out = jnp.roll(out, -k * dk, axis=ax)
        return out

    for side in (0, 1):
        np.testing.assert_array_equal(
            np.asarray(K._ppm_limited(at, side)),
            np.asarray(_ppm_limited_jnp(at_jnp, side)))
    np.testing.assert_array_equal(
        np.asarray(K._central_upwind(uL, uR, axis, 1.4)),
        np.asarray(_central_upwind_jnp(uL, uR, axis, 1.4)))


def _kernel_jaxprs(jaxpr):
    """The kernel jaxprs of every ``pallas_call`` in ``jaxpr``."""
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    if eqn.primitive.name == "pallas_call":
                        yield sub
                    yield from _kernel_jaxprs(sub)


def _primitives(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, jax.extend.core.Jaxpr):
                    yield from _primitives(sub)


def test_kernel_stencil_binds_no_nested_jit():
    """The stencil is spelled in lax primitives: a jnp function such as
    ``jnp.where`` or ``jnp.roll`` is a nested ``jit`` per call, the cost
    that made the kernel's trace dominate set-up."""
    cfg = dataclasses.replace(CONFIG, subgrid=4, levels=1)
    p = cfg.padded
    spec = jax.ShapeDtypeStruct((2, cfg.n_fields, p, p, p), jnp.float32)
    jaxpr = jax.make_jaxpr(pallas_batched_body(cfg, 0.0321, "slot_lane"))(
        spec).jaxpr
    kernels = list(_kernel_jaxprs(jaxpr))
    assert len(kernels) == 2                  # Mosaic and interpret branch
    for k in kernels:
        names = set(_primitives(k))
        assert "select_n" in names and "jit" not in names, names


def test_kernel_call_memoised_by_static_key():
    shape = (32, 5, 14, 14, 14)
    call = rhs_program("slot_lane", shape, F32, H, 1.4, 3, 8)
    assert rhs_program("slot_lane", shape, F32, H, 1.4, 3, 8) is call
    assert rhs_program("slot_lane", (64,) + shape[1:], F32, H, 1.4, 3,
                       8) is not call
    assert rhs_program("slot_lane", shape, F32, H / 2, 1.4, 3, 8) is not call


def test_slot_lane_bucket_padded_to_lane_tiles():
    """A bucket over 128 tasks that 128 does not divide runs as whole
    128-lane tiles, so its VMEM need stays one tile's: 130 tasks give the
    bits of one full tile and one 2-task block run apart."""
    cfg = dataclasses.replace(CONFIG, subgrid=4, levels=1)
    p = cfg.padded
    u = 1.0 + 0.1 * jax.random.uniform(jax.random.PRNGKey(0),
                                       (130, cfg.n_fields, p, p, p))
    body = pallas_batched_body(cfg, H, "slot_lane")
    out = body(u)
    assert out.shape == (130, cfg.n_fields, 4, 4, 4)
    want = jnp.concatenate([body(u[:128]), body(u[128:])])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


def test_tpu_body_through_s4_and_tenant_batcher():
    """The body a TPU selects, in interpret mode, through the sharded
    strategy (``shard_map``) and through merged multi-tenant waves: both
    equal the fused step with the same body bit for bit."""
    cfg = HydroConfig(subgrid=8, ghost=3, levels=1)
    st = sedov_init(cfg)
    dt = courant_dt(st.u, cfg)
    h = UniformSedovScenario(cfg).h
    scen = UniformSedovScenario(
        cfg, batched_body=uniform_batched_body(cfg, h, "tpu"))
    assert _runs_pallas(scen.batched_body, cfg)
    ref = StrategyRunner(scen, AggregationConfig(strategy="fused")
                         ).rk3_step(st.u, dt)
    agg = AggregationConfig(strategy="s4", launch_watermark=10 ** 9)
    out = StrategyRunner(scen, agg).rk3_step(st.u, dt)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    tb = TenantBatcher(ShardedAggregationExecutor(
        config=agg, mesh=subgrid_mesh(1), name="tenancy"))
    for tid in range(2):
        tb.add(tid, scen, st.u, dt)
    merged = tb.rk3_step_all()
    for tid in range(2):
        np.testing.assert_array_equal(np.asarray(merged[tid]),
                                      np.asarray(ref))
