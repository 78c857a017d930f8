"""Scenario protocol: declarative workload descriptions for StrategyRunner.

The execution API splits WHAT from HOW (DESIGN.md §8):

* a **Scenario** (this module) declares WHAT one solver iteration computes —
  its kernel families (id + batched body), the per-iteration task
  populations (parent arrays with a leading task axis, per-task traced
  args), the exchange/assembly steps around them, and the bit-exact fused
  reference every strategy must reproduce;
* a **Strategy** (``repro.core.strategies``) decides HOW those populations
  launch (per-task scatter ring, explicit aggregation, whole-graph fusion).

Adding a workload is one Scenario subclass; it immediately runs under every
registered strategy, and its families aggregate alongside any other
family submitted to the same ``AggregationExecutor``.  Implementations:

* ``UniformSedovScenario`` — the paper's Table II/III workload (one family);
* ``AMRSedovScenario``     — two-level refined Sedov (one or two hydro
  families, per-level traced ``h``);
* ``GravityScenario``      — hydro + per-sub-grid gravity solve: TWO kernel
  families (``hydro_rhs`` + ``gravity``) submitted interleaved through ONE
  executor per iteration, the cross-solver aggregation Octo-Tiger performs
  with its hydro and FMM kernels.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (
    AMRHydroConfig, GravityHydroConfig, HydroConfig,
)
from repro.core.trace import named, program_name
from repro.hydro.state import (
    assemble_global, extract_subgrids, extract_subgrids_multilevel,
    sync_coarse,
)
from repro.hydro.stepper import (
    level_batched_body, level_batched_jit, rk_stage_epilogue,
    stage_coeff_vectors, subgrid_rhs,
)
from repro.kernels.gravity import (
    gravity_batched_body, gravity_batched_jit, gravity_source_update,
)
from repro.kernels.hydro_rhs import pallas_batched_body


def xla_task_body(cfg: HydroConfig, h: float) -> Callable:
    """The fine-grained hydro task body: (F, P, P, P) -> (F, S, S, S)."""
    return partial(subgrid_rhs, h=h, gamma=cfg.gamma,
                   ghost=cfg.ghost, subgrid=cfg.subgrid)


# (subgrid, ghost, dtype) at which the slot_lane Pallas kernel is measured
# on a TPU v5e to fit its scoped VMEM (9.3 MB per 128-task tile) and to
# equal the jnp body bit for bit (at 32, 128 and 512 tasks).
SLOT_LANE_SHAPE = (8, 3, "float32")


def uniform_batched_body(cfg: HydroConfig, h: float,
                         platform: str) -> Callable:
    """The batched body a uniform hydro scenario runs on ``platform``: the
    ``slot_lane`` Pallas kernel on a TPU at a sub-grid shape where it is
    measured to fit and to match, else the vmapped jnp task body."""
    if (platform == "tpu" and (cfg.subgrid, cfg.ghost, jnp.dtype(cfg.dtype)
                               .name) == SLOT_LANE_SHAPE):
        return pallas_batched_body(cfg, h, "slot_lane")
    return jax.vmap(xla_task_body(cfg, h))


@dataclass(frozen=True)
class KernelFamily:
    """One aggregable kernel family: the ``TaskSignature`` kernel id, its
    batched body ``(*stacked_args) -> stacked_out`` (leading slot axis on
    every arg/out), and optionally a pre-jitted twin (so scenario,
    reference and fused strategy share ONE compiled program).

    ``epilogue`` optionally declares a PER-SLOT epilogue
    ``epilogue(body_out_slot, *extra_slots) -> slot_out`` (e.g. the RK-stage
    axpy) that :func:`stage_family` traces *into* the bucketed program: the
    derived family's batched body is ``vmap(epilogue)(batched_body(*main),
    *extras)``, so gather -> body -> stage update compiles to ONE XLA
    program per bucket while submission stays task-granular (DESIGN.md §9).
    """

    kernel: str
    batched_body: Callable
    jit_body: Optional[Callable] = None
    epilogue: Optional[Callable] = None


def stage_family(fam: KernelFamily, n_body_args: int) -> KernelFamily:
    """Derive the epilogue-fused twin of a family: same aggregation
    substrate, bigger body.  The first ``n_body_args`` of a submission feed
    the body; the rest (per-slot extras, incl. per-task coefficient
    vectors) feed the vmapped epilogue.  Works with any batched body — the
    Pallas kernels included — because composition happens at the batched
    level."""
    if fam.epilogue is None:
        raise ValueError(f"family {fam.kernel!r} declares no epilogue")

    def batched(*args):
        out = fam.batched_body(*args[:n_body_args])
        return jax.vmap(fam.epilogue)(out, *args[n_body_args:])

    return KernelFamily(fam.kernel + "+epi", batched)


def _cached_u0_interiors(scn, u0, v, v_int, extract):
    """``u0`` is invariant across a step's three stages (and IS ``v`` in
    stage 1): extract its interiors once per step, keyed on the ``u0``
    object.  Shared by every scenario's ``stage_populations``."""
    if v is u0:
        scn._u0_int_cache = (u0, v_int)
        return v_int
    cache = getattr(scn, "_u0_int_cache", None)
    if cache is None or cache[0] is not u0:
        cache = (u0, extract(u0))
        scn._u0_int_cache = cache
    return cache[1]


def _coeff_cache(scn) -> dict:
    cache = getattr(scn, "_stage_coeff_cache", None)
    if cache is None:
        cache = scn._stage_coeff_cache = {}
    return cache


@dataclass(frozen=True)
class TaskPopulation:
    """One iteration's submission wave for one family: per-task parent
    arrays (leading task axis; per-task traced args like the cell width
    ride along as 1-D parents).  Task ``i`` consumes ``parents[j][i]``."""

    kernel: str
    parents: Tuple[jax.Array, ...]

    @property
    def n_tasks(self) -> int:
        return self.parents[0].shape[0]

    def submit_to(self, executor):
        """Bulk-submit the whole population as ONE contiguous range entry
        (one ``RangeFuture``) — the population-level fast path over n
        per-task ``submit_indexed`` calls."""
        return executor.submit_range(self.parents, 0, self.n_tasks,
                                     kernel=self.kernel)


class Scenario:
    """Base class / protocol.  Subclasses implement:

    * ``families()``            — static kernel-family declarations;
    * ``populations(state)``    — ghost exchange + decomposition: one
      ``TaskPopulation`` per family, ready to submit;
    * ``assemble(state, outs)`` — per-population batched outputs (population
      order) -> ``d(state)/dt`` with the state's pytree structure;
    * ``warmup_parent_specs()`` — (kernel, parent ShapeDtypeStructs) pairs
      describing the submission waves, for AOT bucket warmup;

    and may override ``finalize_step`` (post-RK3 hook, e.g. the AMR
    coarse-fine sync).  ``reference_rhs`` — ONE jitted launch per family
    through the same assemble path — is the bit-exact oracle every
    strategy must match; it is shared code, not per-scenario, so
    runner-vs-reference equivalence reduces to per-family kernel
    equivalence (the aggregation substrate's invariant).
    """

    name: str = "scenario"

    # -- required ----------------------------------------------------------
    def families(self) -> Tuple[KernelFamily, ...]:
        raise NotImplementedError

    def populations(self, state) -> Tuple[TaskPopulation, ...]:
        raise NotImplementedError

    def assemble(self, state, outs: Sequence[Any]):
        raise NotImplementedError

    def warmup_parent_specs(self) -> Tuple[Tuple[str, Tuple[Any, ...]], ...]:
        return ()

    # -- optional: epilogue-fused RK stages (DESIGN.md §9) -----------------
    def stage_families(self) -> Tuple[KernelFamily, ...]:
        """Epilogue-fused twins of the families that declare one; empty when
        the scenario does not support fused stages."""
        return ()

    def stage_populations(self, u0, v, dt, c0,
                          c1) -> Optional[Tuple[TaskPopulation, ...]]:
        """Submission waves whose launches produce the NEXT RK stage state
        per slot: ``out = c0*u0 + c1*(v + dt*rhs(v))`` (Shu-Osher form;
        stage 1 is ``c0=0, c1=1``).  ``None`` = not supported — the runner
        falls back to rhs() + global combine."""
        return None

    def assemble_stage(self, state, outs: Sequence[Any], dt, c0, c1):
        """Per-population stage outputs (population order) -> the next
        stage's state pytree.  The stage coefficients ride along because
        cross-family couplings (e.g. gravity's ``c1*dt`` source tail) are
        applied HERE, after all of the wave's launches — a per-slot
        epilogue cannot see another family's output."""
        raise NotImplementedError

    def stage_warmup_parent_specs(self):
        """Like ``warmup_parent_specs`` for the stage families' waves."""
        return ()

    def reference_stage(self, u0, v, dt, c0, c1):
        """Bit-exact fused reference for one epilogue-fused RK stage: ONE
        jitted launch of each stage family through the same assemble path.
        The oracle the aggregated stage path must match bit-identically —
        same traced composition, only the batch decomposition differs."""
        pops = self.stage_populations(u0, v, dt, c0, c1)
        if pops is None:
            raise NotImplementedError(
                f"scenario {self.name!r} declares no stage populations")
        outs = [self.jitted_body(p.kernel)(*p.parents) for p in pops]
        return self.assemble_stage(v, outs, dt, c0, c1)

    # -- provided ----------------------------------------------------------
    def finalize_step(self, state):
        """Post-RK3-combine hook; identity unless levels need re-syncing."""
        return state

    def describe_task(self, kernel: str, index: int) -> str:
        """Human-readable identity of one task within a family's submission
        wave, used to enrich containment failures (DESIGN.md §11) — e.g.
        "subgrid (1, 3) of the fine level".  Index is wave-relative (the
        task's position in the family's wave).  Override per scenario; the
        default names the kernel and position."""
        return f"task {index} of family {kernel!r}"

    def family(self, kernel: str) -> KernelFamily:
        cache = getattr(self, "_family_by_kernel", None)
        if cache is None:
            cache = {f.kernel: f
                     for f in self.families() + tuple(self.stage_families())}
            self._family_by_kernel = cache
        return cache[kernel]

    def jitted_body(self, kernel: str) -> Callable:
        """The family's jitted batched body (one shared wrapper per family,
        so reference and fused strategy hit the same compiled programs),
        named ``jit_<kernel>`` on the device."""
        cache: Dict[str, Callable] = getattr(self, "_jit_cache", None)
        if cache is None:
            cache = {}
            self._jit_cache = cache
        fn = cache.get(kernel)
        if fn is None:
            fam = self.family(kernel)
            fn = fam.jit_body or jax.jit(named(fam.batched_body,
                                               program_name(kernel)))
            cache[kernel] = fn
        return fn

    def reference_rhs(self, state):
        """Bit-exact fused per-family reference (and the traced rhs the
        ``lax.scan`` trajectory driver folds over)."""
        pops = self.populations(state)
        outs = [self.jitted_body(p.kernel)(*p.parents) for p in pops]
        return self.assemble(state, outs)


# ---------------------------------------------------------------------------
# Uniform Sedov (the paper's Table II/III workload)
# ---------------------------------------------------------------------------

class UniformSedovScenario(Scenario):
    """AMR-off Sedov blast: one kernel family, one task per sub-grid.

    The cell width is uniform, so it is baked into the body at trace time
    (the single-level fast path).  The default batched body is
    :func:`uniform_batched_body` for the platform of ``jax.devices()[0]``;
    a custom ``body`` (vmapped) or ``batched_body`` replaces it.
    """

    def __init__(self, cfg: HydroConfig, bc: str = "outflow",
                 body: Optional[Callable] = None,
                 batched_body: Optional[Callable] = None):
        self.cfg = cfg
        self.bc = bc
        n = cfg.grids_per_edge * cfg.subgrid
        self.h = cfg.domain / n
        self.body = body or xla_task_body(cfg, self.h)
        if batched_body is None:
            batched_body = (jax.vmap(body) if body is not None else
                            uniform_batched_body(cfg, self.h,
                                                 jax.devices()[0].platform))
        self.batched_body = batched_body
        self.name = cfg.name
        self._dtype = jnp.dtype(cfg.dtype)
        self._families = (KernelFamily("hydro_rhs", self.batched_body,
                                       epilogue=rk_stage_epilogue),)
        self._stage_families = (stage_family(self._families[0], 1),)

    def families(self):
        return self._families

    def populations(self, state):
        subs = extract_subgrids(state, self.cfg.subgrid, self.cfg.ghost,
                                self.bc)
        return (TaskPopulation("hydro_rhs", (subs,)),)

    def assemble(self, state, outs):
        return assemble_global(outs[0], self.cfg.subgrid)

    def warmup_parent_specs(self):
        cfg = self.cfg
        p = cfg.padded
        spec = jax.ShapeDtypeStruct(
            (cfg.n_subgrids, cfg.n_fields, p, p, p), jnp.dtype(cfg.dtype))
        return (("hydro_rhs", (spec,)),)

    # -- epilogue-fused RK stages (DESIGN.md §9) ---------------------------
    def stage_families(self):
        return self._stage_families

    def stage_populations(self, u0, v, dt, c0, c1):
        cfg = self.cfg
        subs = extract_subgrids(v, cfg.subgrid, cfg.ghost, self.bc)
        v_int = extract_subgrids(v, cfg.subgrid, 0, self.bc)
        u0_int = _cached_u0_interiors(
            self, u0, v, v_int,
            lambda u: extract_subgrids(u, cfg.subgrid, 0, self.bc))
        n = subs.shape[0]
        coeffs = stage_coeff_vectors(_coeff_cache(self), dt, c0, c1, n,
                                     self._dtype)
        return (TaskPopulation(
            self._stage_families[0].kernel,
            (subs, v_int, u0_int) + coeffs),)

    def assemble_stage(self, state, outs, dt, c0, c1):
        return assemble_global(outs[0], self.cfg.subgrid)

    def stage_warmup_parent_specs(self):
        cfg = self.cfg
        p, s, n = cfg.padded, cfg.subgrid, cfg.n_subgrids
        dtype = jnp.dtype(cfg.dtype)
        f = cfg.n_fields
        scalar = jax.ShapeDtypeStruct((n,), dtype)
        return ((self._stage_families[0].kernel, (
            jax.ShapeDtypeStruct((n, f, p, p, p), dtype),
            jax.ShapeDtypeStruct((n, f, s, s, s), dtype),
            jax.ShapeDtypeStruct((n, f, s, s, s), dtype),
            scalar, scalar, scalar)),)


# ---------------------------------------------------------------------------
# Two-level AMR Sedov (mixed task population, per-level traced h)
# ---------------------------------------------------------------------------

class AMRSedovScenario(Scenario):
    """Two-level refined Sedov: state is ``(uc, uf)``; every iteration
    yields one population per level with per-task traced ``h``.  Levels
    whose sub-grid shapes agree share one kernel family (the same compiled
    buckets serve both); mixed sizes open two families that aggregate
    concurrently.  ``finalize_step`` re-syncs the covered coarse cells.

    The epilogue-fused stage path (DESIGN.md §10) extends §9 to the
    adaptive workload: each level's family derives a ``stage_family`` twin
    with the per-task traced ``h`` riding straight through the fused body,
    so one compiled bucket still serves every refinement level whose
    sub-grid shapes agree — now with the Shu-Osher axpy fused in.
    """

    def __init__(self, cfg: AMRHydroConfig, bc: str = "outflow"):
        self.cfg = cfg
        self.bc = bc
        self.name = cfg.name
        dtype = jnp.dtype(cfg.dtype)
        self._dtype = dtype
        self._levels = ("coarse", "fine")
        self._subgrid = {"coarse": cfg.coarse_subgrid,
                         "fine": cfg.fine_subgrid}
        self._n_level = {"coarse": cfg.n_subgrids_coarse,
                         "fine": cfg.n_subgrids_fine}
        self._h = {
            "coarse": jnp.full((cfg.n_subgrids_coarse,), cfg.h_coarse, dtype),
            "fine": jnp.full((cfg.n_subgrids_fine,), cfg.h_fine, dtype),
        }
        # one family per DISTINCT sub-grid size; equal sizes share everything
        self._kernel = {lvl: f"hydro_rhs_s{self._subgrid[lvl]}"
                        for lvl in self._levels}
        self._families = tuple(
            KernelFamily(f"hydro_rhs_s{s}",
                         level_batched_body(cfg.gamma, cfg.ghost, s),
                         level_batched_jit(cfg.gamma, cfg.ghost, s),
                         epilogue=rk_stage_epilogue)
            for s in dict.fromkeys(self._subgrid.values()))
        # the level body consumes (subs, h); everything after feeds the
        # vmapped stage epilogue
        self._stage_families = tuple(stage_family(f, 2)
                                     for f in self._families)
        self._stage_kernel = {lvl: self._kernel[lvl] + "+epi"
                              for lvl in self._levels}

    def families(self):
        return self._families

    def populations(self, state):
        uc, uf = state
        subs = dict(zip(self._levels,
                        extract_subgrids_multilevel(uc, uf, self.cfg,
                                                    self.bc)))
        return tuple(
            TaskPopulation(self._kernel[lvl], (subs[lvl], self._h[lvl]))
            for lvl in self._levels)

    def assemble(self, state, outs):
        return tuple(assemble_global(out, self._subgrid[lvl])
                     for lvl, out in zip(self._levels, outs))

    def finalize_step(self, state):
        uc, uf = state
        return sync_coarse(uc, uf, self.cfg), uf

    def warmup_parent_specs(self):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        specs = []
        for lvl in self._levels:
            n = self._n_level[lvl]
            p = self._subgrid[lvl] + 2 * cfg.ghost
            specs.append((self._kernel[lvl], (
                jax.ShapeDtypeStruct((n, cfg.n_fields, p, p, p), dtype),
                jax.ShapeDtypeStruct((n,), dtype))))
        return tuple(specs)

    # -- epilogue-fused RK stages (DESIGN.md §10) --------------------------
    def _interiors(self, state):
        """Per-level interiors of the RAW state arrays — the combine side
        of a stage reads the un-synced levels, exactly as the generic
        ``u1 = v + dt * rhs(v)`` path does (the sync lives inside the
        ghost exchange and in ``finalize_step``)."""
        uc, uf = state
        return {"coarse": extract_subgrids(uc, self.cfg.coarse_subgrid, 0,
                                           self.bc),
                "fine": extract_subgrids(uf, self.cfg.fine_subgrid, 0,
                                         self.bc)}

    def stage_families(self):
        return self._stage_families

    def stage_populations(self, u0, v, dt, c0, c1):
        uc, uf = v
        subs = dict(zip(self._levels,
                        extract_subgrids_multilevel(uc, uf, self.cfg,
                                                    self.bc)))
        v_int = self._interiors(v)
        u0_int = _cached_u0_interiors(self, u0, v, v_int, self._interiors)
        cache = _coeff_cache(self)
        pops = []
        for lvl in self._levels:
            coeffs = stage_coeff_vectors(cache, dt, c0, c1,
                                         self._n_level[lvl], self._dtype)
            pops.append(TaskPopulation(
                self._stage_kernel[lvl],
                (subs[lvl], self._h[lvl], v_int[lvl], u0_int[lvl]) + coeffs))
        return tuple(pops)

    def assemble_stage(self, state, outs, dt, c0, c1):
        return tuple(assemble_global(out, self._subgrid[lvl])
                     for lvl, out in zip(self._levels, outs))

    def stage_warmup_parent_specs(self):
        cfg = self.cfg
        dtype = self._dtype
        specs = []
        for lvl in self._levels:
            n, s = self._n_level[lvl], self._subgrid[lvl]
            p = s + 2 * cfg.ghost
            scalar = jax.ShapeDtypeStruct((n,), dtype)
            specs.append((self._stage_kernel[lvl], (
                jax.ShapeDtypeStruct((n, cfg.n_fields, p, p, p), dtype),
                scalar,
                jax.ShapeDtypeStruct((n, cfg.n_fields, s, s, s), dtype),
                jax.ShapeDtypeStruct((n, cfg.n_fields, s, s, s), dtype),
                scalar, scalar, scalar)))
        return tuple(specs)


# ---------------------------------------------------------------------------
# Self-gravitating Sedov (cross-solver aggregation: hydro + gravity)
# ---------------------------------------------------------------------------

@jax.jit
def _apply_gravity_source(u, dudt, pg):
    """Couple the gravity family's output into the hydro RHS: momentum
    gains ``rho * g`` and energy gains ``S . g``.  ONE shared jitted code
    path for runner and reference, so bit-exactness reduces to per-family
    kernel equivalence."""
    return gravity_source_update(u, dudt, pg)


@jax.jit
def _apply_gravity_stage_source(v, staged, pg, c1dt):
    """Couple gravity into an epilogue-fused stage (DESIGN.md §10).  The
    hydro stage family already produced ``c0*u0 + c1*(v + dt*dudt)``; the
    gravity tail of the full update enters as its algebraic remainder,
    ``+ c1*dt * src(v, pg)``.  ONE shared jitted path for runner and
    reference (the aggregated stage wave and ``reference_stage`` both
    land here), so stage bit-exactness again reduces to per-family kernel
    equivalence."""
    return gravity_source_update(v, staged, pg, scale=c1dt)


class GravityScenario(Scenario):
    """Sedov blast under self-gravity: TWO kernel families per iteration.

    Both families consume the SAME ghost-exchanged sub-grid decomposition
    (one parent array feeds hydro and gravity tasks alike, staged by slot
    index) and both take the cell width as a traced per-task argument.
    Under s3/s2+s3 their tasks are submitted interleaved into one
    ``AggregationExecutor``: the region registry routes them by kernel id
    into two concurrent ``TaskSignature`` families with independent bucket
    ladders — the cross-solver aggregation the redesign exists to unlock.

    The epilogue-fused stage path (DESIGN.md §10) is the TWO-FAMILY stage
    protocol: each RK stage submits the hydro family's epilogue-fused twin
    (gather -> Reconstruct+Flux -> Shu-Osher axpy, one program per bucket)
    AND the unchanged gravity relaxation interleaved in the SAME wave; the
    cross-family coupling — which no per-slot epilogue can see, the
    gravity output being a different launch — enters at ``assemble_stage``
    as the algebraically equivalent ``+ c1*dt * src(v, pg)`` tail, through
    one jitted path shared with ``reference_stage``.
    """

    def __init__(self, cfg: GravityHydroConfig, bc: str = "outflow"):
        self.cfg = cfg
        self.bc = bc
        self.name = cfg.name
        hc = cfg.hydro
        self.h = hc.domain / (hc.grids_per_edge * hc.subgrid)
        self._dtype = jnp.dtype(hc.dtype)
        self._h_vec = jnp.full((hc.n_subgrids,), self.h, self._dtype)
        self._families = (
            KernelFamily("hydro_rhs",
                         level_batched_body(hc.gamma, hc.ghost, hc.subgrid),
                         epilogue=rk_stage_epilogue),
            KernelFamily("gravity",
                         gravity_batched_body(hc.ghost, hc.subgrid,
                                              cfg.g_const, cfg.relax_iters),
                         gravity_batched_jit(hc.ghost, hc.subgrid,
                                             cfg.g_const, cfg.relax_iters)),
        )
        # hydro body consumes (subs, h); gravity joins the stage wave as
        # itself (its launches carry no per-slot epilogue to fuse)
        self._stage_families = (stage_family(self._families[0], 2),)

    def families(self):
        return self._families

    def populations(self, state):
        hc = self.cfg.hydro
        subs = extract_subgrids(state, hc.subgrid, hc.ghost, self.bc)
        return (TaskPopulation("hydro_rhs", (subs, self._h_vec)),
                TaskPopulation("gravity", (subs, self._h_vec)))

    def assemble(self, state, outs):
        hc = self.cfg.hydro
        dudt = assemble_global(outs[0], hc.subgrid)
        pg = assemble_global(outs[1], hc.subgrid)
        return _apply_gravity_source(state, dudt, pg)

    def warmup_parent_specs(self):
        hc = self.cfg.hydro
        p = hc.padded
        subs = jax.ShapeDtypeStruct(
            (hc.n_subgrids, hc.n_fields, p, p, p), self._dtype)
        h = jax.ShapeDtypeStruct((hc.n_subgrids,), self._dtype)
        return (("hydro_rhs", (subs, h)), ("gravity", (subs, h)))

    # -- two-family epilogue-fused RK stages (DESIGN.md §10) ---------------
    def stage_families(self):
        return self._stage_families

    def stage_populations(self, u0, v, dt, c0, c1):
        hc = self.cfg.hydro
        subs = extract_subgrids(v, hc.subgrid, hc.ghost, self.bc)
        v_int = extract_subgrids(v, hc.subgrid, 0, self.bc)
        u0_int = _cached_u0_interiors(
            self, u0, v, v_int,
            lambda u: extract_subgrids(u, hc.subgrid, 0, self.bc))
        coeffs = stage_coeff_vectors(_coeff_cache(self), dt, c0, c1,
                                     hc.n_subgrids, self._dtype)
        return (
            TaskPopulation(
                self._stage_families[0].kernel,
                (subs, self._h_vec, v_int, u0_int) + coeffs),
            TaskPopulation("gravity", (subs, self._h_vec)),
        )

    def assemble_stage(self, state, outs, dt, c0, c1):
        hc = self.cfg.hydro
        staged = assemble_global(outs[0], hc.subgrid)
        pg = assemble_global(outs[1], hc.subgrid)
        return _apply_gravity_stage_source(state, staged, pg, c1 * dt)

    def stage_warmup_parent_specs(self):
        hc = self.cfg.hydro
        n, s, p = hc.n_subgrids, hc.subgrid, hc.padded
        dtype = self._dtype
        scalar = jax.ShapeDtypeStruct((n,), dtype)
        subs = jax.ShapeDtypeStruct((n, hc.n_fields, p, p, p), dtype)
        return (
            (self._stage_families[0].kernel, (
                subs, scalar,
                jax.ShapeDtypeStruct((n, hc.n_fields, s, s, s), dtype),
                jax.ShapeDtypeStruct((n, hc.n_fields, s, s, s), dtype),
                scalar, scalar, scalar)),
            ("gravity", (subs, scalar)),
        )
