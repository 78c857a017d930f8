"""``s2``: implicit aggregation — one launch per task, round-robin over a
pre-allocated executor pool; the runtime is left to overlap them (paper
finding: works iff the runtime can — reproduced here).

Each launch slices a task span out of the population's parent arrays and
scatters its result into a donated output slot ring, all inside one
compiled program (``lax.dynamic_slice`` + ``lax.dynamic_update_slice`` on
an in-place buffer) — ZERO host-side slicing or concatenation.  The
classic s2 runs the body at width 1 (one task per launch, the paper's
implicit aggregation); under ``cost_model=True`` the scatter-ring sizing
is *measured* (DESIGN.md §12): the per-width scatter program is timed at
warm-up and the coalesce width minimizing the predicted per-wave wall
time is chosen — same body, same values, fewer launches.  Every width is
bit-identical to width 1 by the bucket invariant (the batched body is
elementwise over the slot axis).

Tradeoff: the donated carry chains launches at the device level, which
costs nothing on XLA:CPU/TPU (one program at a time per core — only host
dispatch pipelining matters, and enqueues still return immediately) but
would forfeit inter-stream concurrency on a CUDA-like backend; DESIGN.md §3.

Stats parity (DESIGN.md §12): per-family launch counters, width
histograms and the measured s2 cost table land in
``ctx.stats["regions"][fam]`` under the same family keys the aggregation
executor uses, so s2 rows in the BENCH files are comparable
family-by-family with s3/mixed rows.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.aggregation import (
    BucketCostModel, TaskSignature, make_s2_scatter, measure_s2_widths,
    s2_width_candidates,
)
from repro.core.strategies.base import RunContext, Strategy, register_strategy
from repro.core.trace import span


@register_strategy("s2")
class S2Strategy(Strategy):
    name = "s2"

    def _plan_for(self, scenario, pop, ctx: RunContext):
        """The per-(kernel, parent shapes) launch plan: chosen coalesce
        width, compiled scatter programs, output-ring spec and the
        family's stats dict.  Built once, cached on the run context."""
        shapes = tuple((tuple(p.shape), str(p.dtype)) for p in pop.parents)
        key = ("s2_plan", pop.kernel, shapes)
        plan = ctx.caches.get(key)
        if plan is not None:
            return plan
        fam = scenario.family(pop.kernel)
        task_specs = tuple(jax.ShapeDtypeStruct(p.shape[1:], p.dtype)
                           for p in pop.parents)
        desc = TaskSignature.from_args(pop.kernel, task_specs).describe()
        spec = jax.eval_shape(fam.batched_body, *pop.parents)
        stats = ctx.stats.setdefault("regions", {}).setdefault(
            desc, {"submitted": 0, "launches": 0, "aggregated_hist": {}})
        width, scatters = 1, {}
        if getattr(ctx.config, "cost_model", False):
            model = None
            exe = getattr(ctx, "executor", None)
            if exe is not None:
                # under ``mixed`` the executor already timed the widths at
                # warmup (the table that routed the family here) — reuse
                # it instead of re-compiling every scatter program
                region = exe._primary_region(pop.kernel)
                if region is not None and region.cost.measured("s2"):
                    model = region.cost
            if model is None:
                model = BucketCostModel()
                times = measure_s2_widths(
                    fam.batched_body, pop.parents,
                    s2_width_candidates(pop.n_tasks),
                    samples=max(1,
                                int(getattr(ctx.config, "cost_samples", 3))),
                    cache=scatters)
                for w, t in times.items():
                    model.record(w, t, path="s2")
            best = model.predict_s2_wave(pop.n_tasks)
            if best is not None:
                width = best[0]
            if model.measured("s2"):
                stats["cost_model_paths"] = {"s2": model.as_stats("s2")}
        if width not in scatters:
            scatters[width] = make_s2_scatter(fam.batched_body, width)
        if pop.n_tasks % width and 1 not in scatters:
            scatters[1] = make_s2_scatter(fam.batched_body, 1)
        stats["selected_strategy"] = "s2"
        stats["s2_width"] = width
        plan = (width, scatters, spec, stats)
        ctx.caches[key] = plan
        return plan

    def launch_population(self, scenario, pop, ctx: RunContext):
        """Run ONE population through the scatter ring (shared with the
        ``mixed`` router's s2-routed families): width-w launches over the
        divisible span, width-1 over the remainder."""
        width, scatters, spec, stats = self._plan_for(scenario, pop, ctx)
        ring = jnp.zeros(spec.shape, spec.dtype)
        n = pop.n_tasks
        main = n - n % width
        for i in range(0, main, width):
            ring = ctx.pool.get().launch(scatters[width], ring, jnp.int32(i),
                                         *pop.parents, family=pop.kernel,
                                         bucket=width)
        for i in range(main, n):
            ring = ctx.pool.get().launch(scatters[1], ring, jnp.int32(i),
                                         *pop.parents, family=pop.kernel,
                                         bucket=1)
        launches = main // width + (n - main)
        ctx.stats["kernel_launches"] += launches
        stats["submitted"] += n
        stats["launches"] += launches
        hist = stats["aggregated_hist"]
        if main:
            hist[width] = hist.get(width, 0) + main // width
        if n - main:
            hist[1] = hist.get(1, 0) + (n - main)
        return ring

    def run_iteration(self, scenario, state, ctx: RunContext):
        with span("repro.populations", scenario=scenario.name):
            pops = scenario.populations(state)
        outs = [self.launch_population(scenario, pop, ctx) for pop in pops]
        with span("repro.assemble"):
            return scenario.assemble(state, outs)
