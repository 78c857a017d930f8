"""``fused``: the whole-graph upper bound — one jitted launch per family.

What a static whole-graph compiler can do when the task structure is known
ahead of time; the paper's dynamic AMR setting is precisely where this is
NOT generally available.  Uses the scenario's shared jitted bodies, so the
fused strategy IS the bit-exact reference (``Scenario.reference_rhs``) by
construction.
"""
from __future__ import annotations

from repro.core.strategies.base import RunContext, Strategy, register_strategy
from repro.core.trace import span


@register_strategy("fused")
class FusedStrategy(Strategy):
    name = "fused"

    @staticmethod
    def _launch(scenario, pop, ctx: RunContext):
        with span("repro.dispatch", kernel=pop.kernel, bucket=pop.n_tasks):
            out = scenario.jitted_body(pop.kernel)(*pop.parents)
        ctx.stats["kernel_launches"] += 1
        return out

    def run_iteration(self, scenario, state, ctx: RunContext):
        with span("repro.populations", scenario=scenario.name):
            pops = scenario.populations(state)
        outs = [self._launch(scenario, pop, ctx) for pop in pops]
        with span("repro.assemble"):
            return scenario.assemble(state, outs)

    def run_stage(self, scenario, u0, v, dt, c0, c1, ctx: RunContext):
        """The fused stage IS the scenario's bit-exact stage reference
        (one jitted launch of each epilogue-fused family)."""
        with span("repro.populations", scenario=scenario.name):
            pops = scenario.stage_populations(u0, v, dt, c0, c1)
        if pops is None:
            return None
        outs = [self._launch(scenario, pop, ctx) for pop in pops]
        with span("repro.assemble"):
            return scenario.assemble_stage(v, outs, dt, c0, c1)
