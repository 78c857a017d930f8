"""``s3`` / ``s2+s3``: explicit on-the-fly aggregation through the
multi-region ``AggregationExecutor``.

Tasks from ALL of the scenario's populations are submitted **interleaved**
into ONE executor: the region registry routes each task by ``TaskSignature``
to its family's slot ring / queue / bucket ladder, so heterogeneous families
— coarse+fine AMR levels, or the hydro and gravity solvers — aggregate
concurrently instead of serializing.  Device staging submits each population
as ONE bulk range entry (``TaskPopulation.submit_to`` ->
``AggregationExecutor.submit_range``): the per-task Python loop — n
``TaskFuture`` allocations, n signature routings, n queue appends per wave —
collapses to one queue entry per family backed by one ``RangeFuture``, and
``gather_futures`` hands the full-range batch back zero-copy.  Populations
that SHARE a kernel (e.g. two AMR levels with equal sub-grid shapes) submit
their ranges sequentially: a launch gathers from one parent set, so the
executor's parent-switch flush keeps each population's buckets whole.
``s2+s3`` is the same strategy over a multi-executor pool (the paper's best
rows).

The seed's slice -> host-stack -> launch cycle survives as
``staging="host"`` (per-task submissions, measurable baseline for
benchmarks/launch_overhead.py).  When the scenario declares per-slot
epilogues, ``run_stage`` drives whole RK stages through the epilogue-fused
twin families (DESIGN.md §9) — and a stage wave may carry SEVERAL
families at once: the AMR scenario submits one range per level twin, the
gravity scenario its hydro twin AND the plain gravity family interleaved
in the same wave (DESIGN.md §10), with any cross-family coupling applied
by ``assemble_stage``.  Stats report per-call DELTAS — the executor's own
counters are cumulative, so the wave is snapshotted around the
submissions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.aggregation import gather_futures
from repro.core.faults import LaunchTimeoutError, TaskFailedError
from repro.core.strategies.base import RunContext, Strategy, register_strategy
from repro.core.trace import span


@register_strategy("s3", "s2+s3")
class S3Strategy(Strategy):
    name = "s3"
    uses_executor = True

    def _submit_populations(self, exe, pops, host: bool):
        """One wave: bulk range per population (device staging), round-robin
        per-task interleave across families (host staging).  Launches the
        submissions trigger nest under the ``repro.submit`` span."""
        with span("repro.submit"):
            futs = [[] for _ in pops]
            if not host:
                # one range entry per population; same-kernel populations stay
                # contiguous by construction (each range is one entry)
                for pi, pop in enumerate(pops):
                    if pop.n_tasks:
                        futs[pi].append(pop.submit_to(exe))
                return futs
            # flatten each kernel family's populations into one ordered task
            # list, then round-robin one submission per family per turn
            lanes = {}
            for pi, pop in enumerate(pops):
                lanes.setdefault(pop.kernel, []).extend(
                    (pi, pop, i) for i in range(pop.n_tasks))
            cursors = [iter(lane) for lane in lanes.values()]
            while cursors:
                live = []
                for cur in cursors:                   # interleave the families
                    nxt = next(cur, None)
                    if nxt is None:
                        continue
                    pi, pop, i = nxt
                    futs[pi].append(exe.submit(
                        *(par[i] for par in pop.parents), kernel=pop.kernel))
                    live.append(cur)
                cursors = live
            return futs

    def _drain(self, scenario, exe, pops, futs):
        try:
            with span("repro.flush"):
                exe.flush()
        except LaunchTimeoutError as err:
            # the flush-time watchdog caught a REAL hang (DESIGN.md §14):
            # name the wave's families so the timeout is attributable —
            # unlike an injected hang at dispatch, the futures of the hung
            # launch were already fulfilled and cannot be retried here
            fams = sorted({pop.kernel for pop in pops if pop.n_tasks})
            raise LaunchTimeoutError(
                f"watchdog timeout while draining wave of families "
                f"{fams}: {err}") from err
        # a population may legitimately be empty this iteration (dynamic
        # task structure, e.g. a refinement level with no patches): hand
        # assemble a zero-length batch instead of gathering nothing
        outs = []
        with span("repro.gather"):
            for pop, f in zip(pops, futs):
                if f:
                    try:
                        outs.append(gather_futures(f))
                    except TaskFailedError as err:
                        # translate the executor's wave-relative task ids
                        # into the scenario's own vocabulary before
                        # propagating — the physicist debugging a tripped
                        # wave should read "subgrid (i, j)", not a slot
                        # number (DESIGN.md §11)
                        what = ", ".join(
                            scenario.describe_task(pop.kernel, tid)
                            for tid in err.task_ids) or "unknown task"
                        raise TaskFailedError(
                            f"{what} failed during aggregated execution: "
                            f"{err}",
                            task_ids=err.task_ids,
                            kernel=pop.kernel) from err
                else:
                    spec = jax.eval_shape(
                        scenario.family(pop.kernel).batched_body,
                        *pop.parents)
                    outs.append(jnp.zeros(spec.shape, spec.dtype))
        return outs

    def run_iteration(self, scenario, state, ctx: RunContext):
        exe = ctx.executor
        with span("repro.populations", scenario=scenario.name):
            pops = scenario.populations(state)
        before_launches = exe.stats["launches"]
        before_staging = exe.stats["staging_s"]
        futs = self._submit_populations(exe, pops,
                                        host=ctx.config.staging == "host")
        outs = self._drain(scenario, exe, pops, futs)
        ctx.stats["staging_s"] += exe.stats["staging_s"] - before_staging
        ctx.stats["kernel_launches"] += (exe.stats["launches"]
                                         - before_launches)
        with span("repro.assemble"):
            return scenario.assemble(state, outs)

    def run_stage(self, scenario, u0, v, dt, c0, c1, ctx: RunContext):
        if ctx.config.staging == "host":
            return None                  # baseline path stays per-task
        with span("repro.populations", scenario=scenario.name):
            pops = scenario.stage_populations(u0, v, dt, c0, c1)
        if pops is None:
            return None
        exe = ctx.executor
        before_launches = exe.stats["launches"]
        before_staging = exe.stats["staging_s"]
        futs = self._submit_populations(exe, pops, host=False)
        outs = self._drain(scenario, exe, pops, futs)
        ctx.stats["staging_s"] += exe.stats["staging_s"] - before_staging
        ctx.stats["kernel_launches"] += (exe.stats["launches"]
                                         - before_launches)
        with span("repro.assemble"):
            return scenario.assemble_stage(v, outs, dt, c0, c1)
