"""The one execution facade: ``StrategyRunner(scenario, agg)``.

Replaces the legacy per-workload runners (``HydroStrategyRunner`` /
``AMRStrategyRunner`` survive below as deprecation shims): the runner owns
the executor pool, the (optional) multi-region ``AggregationExecutor``
with every scenario family registered, the unified stats, and the
scenario-agnostic drivers — RK3 stepping over arbitrary state pytrees,
AOT bucket warmup, and the ``lax.scan`` whole-trajectory program (now
uniform across scenarios, AMR included).

Strategy names are validated against the plugin registry at CONSTRUCTION
(listing the valid names on error), not on the first ``rhs()`` call.
"""
from __future__ import annotations

import time
import warnings
from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import (
    AMRHydroConfig, AggregationConfig, HydroConfig,
)
from repro.checkpoint.ckpt import (
    latest_step, restore_checkpoint, save_checkpoint,
)
from repro.core.aggregation import AggregationExecutor, greedy_decomposition
from repro.core.executor import ExecutorPool
from repro.core.faults import FaultInjector, NonFiniteStateError, all_finite
from repro.core.scenario import (
    AMRSedovScenario, Scenario, UniformSedovScenario,
)
from repro.core.strategies.base import RunContext, get_strategy_class
from repro.core.trace import span


class StrategyRunner:
    """Drives any :class:`~repro.core.scenario.Scenario` under any
    registered strategy.  ``state`` is whatever pytree the scenario
    defines (a bare array for the uniform grid, ``(uc, uf)`` for AMR).

    ``stats`` is the unified observability surface: ``kernel_launches`` /
    ``iterations`` / ``staging_s`` accumulate per-call deltas for every
    strategy, and — when an aggregation executor exists — ``regions`` is a
    live view of the per-``TaskSignature``-family bucket histograms.
    Per-family launch counts are on ``launches_by_family``.  Host spans
    (``repro.step`` down to ``repro.dispatch``) are in ``core/trace.py``.
    """

    def __init__(self, scenario: Scenario, agg: AggregationConfig,
                 fault_injector: Optional[FaultInjector] = None):
        strategy_cls = get_strategy_class(agg.strategy)   # fail fast
        self.scenario = scenario
        self.agg = agg
        self.strategy = agg.strategy
        self._strategy = strategy_cls()
        self._guard = getattr(agg, "guard", "off")
        if self._guard not in ("off", "finite"):
            raise ValueError(
                f"guard={self._guard!r} — expected 'off' or 'finite'")
        self.pool = ExecutorPool(max(1, agg.n_executors))
        self._agg_exec: Optional[AggregationExecutor] = None
        self.stats: Dict[str, Any] = {"kernel_launches": 0, "iterations": 0,
                                      "staging_s": 0.0}
        self._validate_family_strategies(scenario, agg)
        if strategy_cls.uses_executor:
            exe_cls = strategy_cls.executor_cls or AggregationExecutor
            self._agg_exec = exe_cls(
                None, agg, pool=self.pool, name=scenario.name,
                fault_injector=fault_injector)
            for fam in scenario.families():
                self._agg_exec.register(fam.kernel, fam.batched_body)
            for fam in scenario.stage_families():
                self._agg_exec.register(fam.kernel, fam.batched_body)
            self.stats["regions"] = self._agg_exec.stats["regions"]
        else:
            # stats parity (DESIGN.md §12): executor-less strategies (s2 /
            # fused) publish per-family counters under the same key, so
            # the BENCH observability surface is strategy-independent
            self.stats["regions"] = {}
        self.ctx = RunContext(config=agg, pool=self.pool,
                              executor=self._agg_exec, stats=self.stats)
        # epilogue-fused RK stages (DESIGN.md §9): opt-in via config, only
        # when the scenario declares stage populations AND the strategy
        # overrides run_stage AND staging is device-resident — deciding
        # here (not at the first step) keeps warmup() warming the families
        # the run will actually launch
        from repro.core.strategies.base import Strategy as _StrategyBase
        strategy_has_stage = (type(self._strategy).run_stage
                              is not _StrategyBase.run_stage)
        self._fuse_epilogue = (getattr(agg, "fuse_epilogue", False)
                               and bool(scenario.stage_families())
                               and strategy_has_stage
                               and agg.staging != "host")
        self._traj_cache: Dict[int, Callable] = {}

    @staticmethod
    def _validate_family_strategies(scenario: Scenario,
                                    agg: AggregationConfig) -> None:
        """Fail fast on a bad ``family_strategies`` mapping: every value
        must be a valid route, every key a kernel the scenario can launch
        (plain or stage family, a "+epi" twin's base, or "*")."""
        fs = getattr(agg, "family_strategies", None)
        if not fs:
            return
        from repro.configs.base import FAMILY_STRATEGY_CHOICES
        known = {f.kernel for f in scenario.families()}
        known |= {f.kernel for f in scenario.stage_families()}
        valid_keys = known | {"*"}
        for kernel, choice in fs.items():
            if choice not in FAMILY_STRATEGY_CHOICES:
                raise ValueError(
                    f"family_strategies[{kernel!r}] = {choice!r} — valid "
                    f"assignments: {FAMILY_STRATEGY_CHOICES}")
            if kernel not in valid_keys:
                raise ValueError(
                    f"family_strategies key {kernel!r} names no kernel "
                    f"family of scenario {scenario.name!r} — known "
                    f"families: {sorted(known)} (or '*')")

    # -- observability -----------------------------------------------------
    @property
    def executor(self) -> Optional[AggregationExecutor]:
        """The multi-region aggregation executor (s3/s2+s3), else None."""
        return self._agg_exec

    def set_fault_injector(self, injector: Optional[FaultInjector]) -> None:
        """Arm (or disarm with None) deterministic fault injection on the
        aggregation executor.  Executor-less strategies (fused / s2) have
        no injection sites — for them only the runner-level guard applies."""
        if self._agg_exec is not None:
            self._agg_exec.set_fault_injector(injector)

    @property
    def launches_by_family(self) -> dict:
        return self.pool.launches_by_family

    # -- warmup ------------------------------------------------------------
    def warmup(self, wave_only: bool = False,
               store: Optional[Any] = None) -> None:
        """AOT pre-compile every family's gather/prefix buckets from the
        parent shapes the scenario's submission waves will reference
        (shape-agreeing waves are deduplicated).

        ``wave_only=True`` restricts AOT to the buckets a full wave's greedy
        decomposition uses (the steady state under a pinned watermark) —
        the benchmark's compile budget; other buckets compile lazily.  When
        the epilogue-fused stage path is active, only the stage families
        are warmed — the plain families never launch on that path.

        ``store`` (DESIGN.md §13) passes a persistent tune store through
        to the executor: families with a valid stored entry load their
        tuned state instead of measuring it, and bucket compiles become
        persistent-cache disk hits.
        """
        if self._agg_exec is None:
            return
        if self._fuse_epilogue:
            specs = tuple(self.scenario.stage_warmup_parent_specs())
        else:
            specs = tuple(self.scenario.warmup_parent_specs())
        seen = set()
        for kernel, parent_specs in specs:
            key = (kernel, tuple((tuple(p.shape), str(p.dtype))
                                 for p in parent_specs))
            if key in seen:
                continue
            seen.add(key)
            buckets = None
            if wave_only:
                ladder = self._agg_exec.config.bucket_sizes()
                wave = min(p.shape[0] for p in parent_specs)
                buckets = tuple(sorted(set(greedy_decomposition(wave,
                                                                ladder))))
            self._agg_exec.warmup(kernel=kernel, parent_shapes=parent_specs,
                                  buckets=buckets, store=store)

    def save_tuning(self, store: Optional[Any] = None) -> Optional[str]:
        """Persist every tuned family's state into the tune store (the
        config's, or an explicit path/instance).  No-op (returns None)
        for executor-less strategies or when no store is configured."""
        if self._agg_exec is None:
            return None
        return self._agg_exec.save_tuning(store)

    # -- one solver iteration ----------------------------------------------
    def rhs(self, state):
        self.stats["iterations"] += 1
        out = self._strategy.run_iteration(self.scenario, state, self.ctx)
        if self._guard == "finite" and self._agg_exec is None:
            # executor-less strategies (fused / s2) have no per-bucket
            # containment layer — the guard degrades to a whole-iteration
            # tripwire so guard="finite" still means "never silently
            # propagate a non-finite state" under every strategy
            if not all_finite(out):
                raise NonFiniteStateError(
                    f"non-finite rhs output under strategy "
                    f"{self.strategy!r} (iteration "
                    f"{self.stats['iterations']}); executor-less strategies "
                    f"cannot bisect — rerun under s3 to isolate the task")
        return out

    # -- RK3 (three iterations per time-step, as in the paper) -------------
    def rk3_step(self, state, dt):
        with span("repro.step", step=self.stats["iterations"] // 3,
                  strategy=self.strategy):
            if self._fuse_epilogue:
                out = self._rk3_step_fused_stages(state, dt)
                if out is not None:
                    return out
            l0 = self._rhs_stage(0, state)
            u1 = self._combine(lambda u, l: u + dt * l, state, l0)
            l1 = self._rhs_stage(1, u1)
            u2 = self._combine(
                lambda u, a, l: 0.75 * u + 0.25 * (a + dt * l),
                state, u1, l1)
            l2 = self._rhs_stage(2, u2)
            out = self._combine(
                lambda u, a, l: (1.0 / 3.0) * u + (2.0 / 3.0) * (a + dt * l),
                state, u2, l2)
            with span("repro.combine"):
                return self.scenario.finalize_step(out)

    def _rhs_stage(self, stage: int, state):
        with span("repro.rk_stage", stage=stage):
            return self.rhs(state)

    @staticmethod
    def _combine(fn, *trees):
        """One RK axpy over the state pytrees (eager device ops)."""
        with span("repro.combine"):
            return jax.tree_util.tree_map(fn, *trees)

    def _rk3_step_fused_stages(self, state, dt):
        """RK3 through the epilogue-fused stage path: each Shu-Osher stage
        is one submission wave of the scenario's stage families — gather,
        body and stage axpy in ONE program per bucket (DESIGN.md §9).
        Returns None (falling back to the generic path) when the strategy
        has no ``run_stage``."""
        sc = self.scenario

        def stage(i, v, c0, c1):
            with span("repro.rk_stage", stage=i):
                return self._strategy.run_stage(sc, state, v, dt, c0, c1,
                                                self.ctx)

        u1 = stage(0, state, 0.0, 1.0)
        if u1 is None:
            self._fuse_epilogue = False       # strategy has no stage path
            return None
        self.stats["iterations"] += 1
        u2 = stage(1, u1, 0.75, 0.25)
        self.stats["iterations"] += 1
        out = stage(2, u2, 1.0 / 3.0, 2.0 / 3.0)
        self.stats["iterations"] += 1
        with span("repro.combine"):
            return sc.finalize_step(out)

    # -- whole-trajectory scan driver (fused upper bound) ------------------
    def _trajectory_impl(self, n_steps: int, state, dt):
        tm = jax.tree_util.tree_map

        def body(s, _):
            l0 = self.scenario.reference_rhs(s)
            u1 = tm(lambda u, l: u + dt * l, s, l0)
            l1 = self.scenario.reference_rhs(u1)
            u2 = tm(lambda u, a, l: 0.75 * u + 0.25 * (a + dt * l),
                    s, u1, l1)
            l2 = self.scenario.reference_rhs(u2)
            out = tm(lambda u, a, l: (1.0 / 3.0) * u
                     + (2.0 / 3.0) * (a + dt * l), s, u2, l2)
            return self.scenario.finalize_step(out), None

        out, _ = jax.lax.scan(body, state, None, length=n_steps)
        return out

    def rk3_trajectory(self, state, dt, n_steps: int):
        """Run ``n_steps`` RK3 steps.  Under ``fused`` the whole trajectory
        is ONE donated ``lax.scan`` program (single dispatch, state updated
        in place) — for EVERY scenario, AMR included; other strategies
        fall back to the per-step loop."""
        if self.strategy != "fused":
            for _ in range(n_steps):
                state = self.rk3_step(state, dt)
            return state
        fn = self._traj_cache.get(n_steps)
        if fn is None:
            fn = jax.jit(partial(self._trajectory_impl, n_steps),
                         donate_argnums=(0,))
            self._traj_cache[n_steps] = fn
        # donate a private copy so the caller's state stays valid; inside
        # the program the scan carry aliases the donated buffers
        out = fn(jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True),
                                        state), dt)
        self.stats["kernel_launches"] += 1
        self.stats["iterations"] += 3 * n_steps
        return out

    # -- crash-consistent trajectory driver (DESIGN.md §14) ----------------
    def run(self, state, dt, n_steps: int, *, checkpoint_every: int = 0,
            ckpt_dir: Optional[str] = None, start_step: int = 0):
        """Drive ``n_steps`` RK3 steps, persisting a crash-consistent
        checkpoint every ``checkpoint_every`` completed steps (atomic npz
        state + step index via ``checkpoint/ckpt.py``, plus a tune-store
        save so a restarted process warm-starts).  A process killed
        mid-run loses at most ``checkpoint_every - 1`` steps of work:
        :meth:`resume` restores the latest complete checkpoint and
        continues the trajectory bit-identically (RK3 stepping is
        deterministic and fp32 state round-trips npz exactly)."""
        cadence = max(0, int(checkpoint_every)) if ckpt_dir else 0
        for step in range(int(start_step), int(n_steps)):
            state = self.rk3_step(state, dt)
            done = step + 1
            if cadence and (done % cadence == 0 or done == n_steps):
                self._checkpoint(ckpt_dir, done, state, dt, n_steps,
                                 cadence)
        return state

    def _checkpoint(self, ckpt_dir: str, step: int, state, dt,
                    n_steps: int, checkpoint_every: int) -> None:
        jax.block_until_ready(state)
        meta = {"dt": float(dt), "n_steps": int(n_steps),
                "checkpoint_every": int(checkpoint_every),
                "scenario": self.scenario.name, "strategy": self.strategy}
        save_checkpoint(ckpt_dir, step, state, {}, meta=meta)
        # tuning rides the same cadence: the resumed process restores its
        # ladders/cost tables from the store instead of re-measuring
        self.save_tuning()

    def resume(self, ckpt_dir: str, state_template, *,
               dt: Optional[float] = None, n_steps: Optional[int] = None,
               checkpoint_every: int = 0):
        """Continue a killed :meth:`run` from its latest complete
        checkpoint: restore the state pytree against ``state_template``
        (the scenario's initial condition works — only structure/dtypes
        matter), re-warm the executor (store-aware, so a configured tune
        store yields ``warm_start=True`` with zero measurement launches),
        and run the remaining steps under the SAME cadence.  ``dt`` /
        ``n_steps`` default to the values recorded at save time.
        ``stats["resumed_from_step"]`` / ``stats["recovery_steps"]``
        record where the trajectory picked up and how many steps remained
        — the chaos-soak recovery metric."""
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(
                f"no checkpoint to resume under {ckpt_dir!r}")
        state, _, meta = restore_checkpoint(ckpt_dir, step, state_template,
                                            {})
        dt = meta["dt"] if dt is None else dt
        n_steps = int(meta["n_steps"]) if n_steps is None else int(n_steps)
        cadence = int(checkpoint_every
                      or meta.get("checkpoint_every", 0))
        self.warmup()
        self.stats["resumed_from_step"] = step
        self.stats["recovery_steps"] = max(0, n_steps - step)
        return self.run(state, dt, n_steps, checkpoint_every=cadence,
                        ckpt_dir=ckpt_dir, start_step=step)

    def time_step(self, state, dt, n_steps: int = 1,
                  use_scan: bool = False) -> float:
        """Average wall seconds per time-step (the Table III metric)."""
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        if use_scan and self.strategy == "fused":
            out = self.rk3_trajectory(state, dt, n_steps)
        else:
            out = state
            for _ in range(n_steps):
                out = self.rk3_step(out, dt)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / n_steps


# ---------------------------------------------------------------------------
# deprecation shims over the facade (state/call conventions are the new
# ones: the AMR runner's state is a (uc, uf) tuple)
# ---------------------------------------------------------------------------

def HydroStrategyRunner(cfg: HydroConfig, agg: AggregationConfig,
                        bc: str = "outflow", body=None, batched_body=None):
    """Deprecated: ``StrategyRunner(UniformSedovScenario(cfg), agg)``."""
    warnings.warn(
        "HydroStrategyRunner is deprecated — use "
        "StrategyRunner(UniformSedovScenario(cfg), agg)",
        DeprecationWarning, stacklevel=2)
    return StrategyRunner(UniformSedovScenario(cfg, bc=bc, body=body,
                                               batched_body=batched_body), agg)


def AMRStrategyRunner(cfg: AMRHydroConfig, agg: AggregationConfig,
                      bc: str = "outflow"):
    """Deprecated: ``StrategyRunner(AMRSedovScenario(cfg), agg)``."""
    warnings.warn(
        "AMRStrategyRunner is deprecated — use "
        "StrategyRunner(AMRSedovScenario(cfg), agg)",
        DeprecationWarning, stacklevel=2)
    return StrategyRunner(AMRSedovScenario(cfg, bc=bc), agg)
