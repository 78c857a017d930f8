"""The system under test for a uniform Sedov cell: ``StrategyRunner`` over
``UniformSedovScenario``, stepped as ``examples/sedov_blastwave.py`` steps
it.  Every ``AggregationConfig`` field but ``strategy`` keeps the program's
default, so a better default shows in the cell."""
from __future__ import annotations

import jax

from repro.configs.base import AggregationConfig, HydroConfig
from repro.core import StrategyRunner, UniformSedovScenario
from repro.hydro.stepper import courant_dt


class Program:
    def __init__(self, config: dict, traffic: dict):
        self.cfg = HydroConfig(**config["hydro"])
        self.runner = StrategyRunner(
            UniformSedovScenario(self.cfg),
            AggregationConfig(strategy=traffic["strategy"]))

    def warmup(self, u) -> None:
        """Compile (or load) every program the window runs: the wave's
        buckets, one whole step and the Courant reduction."""
        self.runner.warmup(wave_only=True)
        dt = self.dt(u)
        jax.block_until_ready(self.step(u, dt))
        float(dt)

    def dt(self, u):
        return courant_dt(u, self.cfg)

    def step(self, u, dt):
        return self.runner.rk3_step(u, dt)

    def counters(self) -> dict:
        """Cumulative launches, and staging seconds where an aggregation
        executor stages (``None`` under executor-less strategies)."""
        st = self.runner.stats
        staging = st["staging_s"] if self.runner.executor else None
        return {"launches": st["kernel_launches"], "staging_s": staging}

    def faults(self) -> int:
        """Sum of every region's fault counters."""
        n = 0
        for region in self.runner.stats.get("regions", {}).values():
            for v in region.get("faults", {}).values():
                n += len(v) if isinstance(v, (list, tuple)) else int(v)
        return n
