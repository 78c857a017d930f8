"""``mixed``: cost-driven per-family strategy routing (DESIGN.md §12).

Octo-Tiger does not force one launch strategy on every kernel type — the
hydro solver aggregates while gravity runs fused, because per-kernel-type
tuning is what carries real scenarios (the paper's follow-up,
PAPERS.md).  This strategy reproduces that: each :class:`KernelFamily`
routes independently to

* ``"s3"``    — bucketed aggregation through the shared multi-region
                ``AggregationExecutor`` (ranges submitted, ladder drained);
* ``"s2"``    — the donated scatter ring at the measured coalesce width
                (``S2Strategy.launch_population``);
* ``"fused"`` — one jitted whole-family launch.

The route comes from ``AggregationConfig(family_strategies={...})``
(exact kernel id, the ``"+epi"`` twin's base kernel, or the ``"*"``
wildcard), and missing/``"auto"`` entries from the executor's measured
``select_strategy`` — the per-family s2/s3/fused wall-time comparison the
extended :class:`BucketCostModel` makes honest.  Routes resolve once per
run context and are persisted (with the cost numbers that justified
them) into ``stats["regions"][fam]["selected_strategy"]``.

Bit-identity: every route runs the family's SAME traced batched body —
only the batch decomposition differs — so mixed results are bit-identical
to the fused reference for every assignment (tests/test_mixed.py sweeps
the product).

Guard compatibility (DESIGN.md §11 × §12): s3-routed families keep the
executor's full containment (bisection isolates the culprit task);
s2/fused-routed families have no bucket structure to bisect, so the
strategy applies the per-family tripwire itself — a non-finite output
raises :class:`NonFiniteStateError` naming the family and its route.
Injected payload faults fire on non-executor routes too (same
deterministic schedule, wave-relative task ids), so fault tests cover
every route.
"""
from __future__ import annotations

import jax

from repro.configs.base import (
    FAMILY_STRATEGY_CHOICES, resolve_family_option,
)
from repro.core.aggregation import TaskSignature
from repro.core.faults import NonFiniteStateError, all_finite, poison_slots
from repro.core.strategies.base import RunContext, Strategy, register_strategy
from repro.core.strategies.s2 import S2Strategy
from repro.core.strategies.s3 import S3Strategy
from repro.core.trace import span


@register_strategy("mixed")
class MixedStrategy(Strategy):
    name = "mixed"
    uses_executor = True

    def __init__(self):
        self._s2 = S2Strategy()
        self._s3 = S3Strategy()

    # -- routing -----------------------------------------------------------
    def _route(self, kernel: str, ctx: RunContext) -> str:
        # circuit breaker override (DESIGN.md §14): a family whose breaker
        # is not closed is pinned to s3 for THIS wave regardless of its
        # cached route — only the executor path has the bucket-1 floor and
        # bisection a faulting family needs.  The cached route is left
        # untouched: the family returns to it once the breaker closes.
        if ctx.executor.breaker_state(kernel) != "closed":
            return "s3"
        key = ("mixed_route", kernel)
        choice = ctx.caches.get(key)
        if choice is not None:
            return choice
        choice = resolve_family_option(
            getattr(ctx.config, "family_strategies", None), kernel, "auto")
        if choice not in FAMILY_STRATEGY_CHOICES:
            raise ValueError(
                f"family_strategies[{kernel!r}] = {choice!r} — valid "
                f"assignments: {FAMILY_STRATEGY_CHOICES}")
        if choice == "auto":
            choice = ctx.executor.select_strategy(kernel)
        else:
            ctx.executor.record_selection(kernel, choice)
        ctx.caches[key] = choice
        return choice

    def routes(self, scenario, ctx: RunContext) -> dict:
        """The resolved per-family assignment (kernel -> strategy) for
        every family the scenario can launch — the BENCH observability
        surface."""
        kernels = [f.kernel for f in scenario.families()]
        kernels += [f.kernel for f in scenario.stage_families()]
        return {k: self._route(k, ctx) for k in kernels}

    # -- one wave ----------------------------------------------------------
    def _run_wave(self, scenario, pops, ctx: RunContext):
        """Route one submission wave: s3 populations enter the executor as
        bulk ranges first (their queue fills while the other routes
        dispatch), then s2/fused populations launch directly on the pool,
        then the executor drains.  Outputs come back in population order."""
        exe = ctx.executor
        routes = [self._route(pop.kernel, ctx) for pop in pops]
        before_launches = exe.stats["launches"]
        before_staging = exe.stats["staging_s"]
        s3_idx = [i for i, r in enumerate(routes) if r == "s3"]
        s3_pops = [pops[i] for i in s3_idx]
        futs = self._s3._submit_populations(
            exe, s3_pops, host=ctx.config.staging == "host")
        outs = [None] * len(pops)
        for i, (pop, route) in enumerate(zip(pops, routes)):
            if route == "s2":
                outs[i] = self._s2.launch_population(scenario, pop, ctx)
            elif route == "fused":
                outs[i] = self._launch_fused(scenario, pop, ctx)
        for i, out in zip(s3_idx, self._s3._drain(scenario, exe, s3_pops,
                                                  futs)):
            outs[i] = out
        ctx.stats["staging_s"] += exe.stats["staging_s"] - before_staging
        ctx.stats["kernel_launches"] += (exe.stats["launches"]
                                         - before_launches)
        self._audit(pops, routes, outs, ctx)
        return outs

    def _launch_fused(self, scenario, pop, ctx: RunContext):
        out = ctx.pool.get().launch(scenario.jitted_body(pop.kernel),
                                    *pop.parents, family=pop.kernel,
                                    bucket=pop.n_tasks)
        ctx.stats["kernel_launches"] += 1
        # stats parity: the same TaskSignature family key the executor and
        # the s2 route use, so BENCH helpers read one key per family
        key = ("mixed_desc", pop.kernel,
               tuple((tuple(p.shape), str(p.dtype)) for p in pop.parents))
        desc = ctx.caches.get(key)
        if desc is None:
            task_specs = tuple(jax.ShapeDtypeStruct(p.shape[1:], p.dtype)
                               for p in pop.parents)
            desc = TaskSignature.from_args(pop.kernel, task_specs).describe()
            ctx.caches[key] = desc
        stats = ctx.stats.setdefault("regions", {}).setdefault(
            desc, {"submitted": 0, "launches": 0,
                   "aggregated_hist": {}})
        stats["submitted"] += pop.n_tasks
        stats["launches"] += 1
        hist = stats["aggregated_hist"]
        hist[pop.n_tasks] = hist.get(pop.n_tasks, 0) + 1
        stats.setdefault("selected_strategy", "fused")
        return out

    def _audit(self, pops, routes, outs, ctx: RunContext) -> None:
        """Fault injection + guard tripwire for the non-executor routes
        (s3-routed families are audited inside the executor's flush)."""
        exe = ctx.executor
        injector = exe._injector
        guard = getattr(ctx.config, "guard", "off") == "finite"
        if injector is None and not guard:
            return
        for i, (pop, route) in enumerate(zip(pops, routes)):
            if route == "s3" or outs[i] is None:
                continue
            if injector is not None:
                wave_key = ("mixed_wave", pop.kernel)
                wave = ctx.caches.get(wave_key, 0)
                ctx.caches[wave_key] = wave + 1
                poisons = injector.poison_positions(
                    pop.kernel, wave, list(range(pop.n_tasks)))
                if poisons:
                    outs[i] = poison_slots(outs[i], sorted(poisons), poisons)
            if guard and not all_finite(outs[i]):
                raise NonFiniteStateError(
                    f"non-finite output in family {pop.kernel!r} routed to "
                    f"{route!r} under 'mixed' — only aggregated (s3-routed) "
                    f"families can bisect; assign the family to 's3' in "
                    f"family_strategies to isolate the task")

    # -- strategy protocol -------------------------------------------------
    def run_iteration(self, scenario, state, ctx: RunContext):
        with span("repro.populations", scenario=scenario.name):
            pops = scenario.populations(state)
        outs = self._run_wave(scenario, pops, ctx)
        with span("repro.assemble"):
            return scenario.assemble(state, outs)

    def run_stage(self, scenario, u0, v, dt, c0, c1, ctx: RunContext):
        if ctx.config.staging == "host":
            return None                  # baseline path stays per-task
        with span("repro.populations", scenario=scenario.name):
            pops = scenario.stage_populations(u0, v, dt, c0, c1)
        if pops is None:
            return None
        outs = self._run_wave(scenario, pops, ctx)
        with span("repro.assemble"):
            return scenario.assemble_stage(v, outs, dt, c0, c1)
