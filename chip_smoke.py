"""Bring-up smoke test: the aggregation runtime's main path on a TPU.

    python3 chip_smoke.py              # one chip: phases A and B
    python3 chip_smoke.py --chips 4    # four chips: phase C only

It drives ``StrategyRunner`` over ``UniformSedovScenario`` through the
entry points a user calls (as ``examples/sedov_blastwave.py`` does), with
the Sedov blast wave made from its closed-form initial condition:

* **A** -- the paper's Table II size (512 sub-grids of 8^3, 262,144 cells):
  3 RK3 steps under ``s3``, ``mixed`` and ``fused``; every result must
  equal the ``fused`` one.
* **B** -- ``levels=4`` (4,096 sub-grids, 2.1 M cells): 3 RK3 steps under
  ``s3``, against a reference that evaluates each wave as ONE launch in
  chunks of 32 tasks (``inner_chunk``), because the whole-wave ``fused``
  program with the jnp body needs more HBM than one chip has.  ``fused``
  is then tried once at this size: it must either equal ``s3`` or be
  refused for memory, loudly, and the line says which.
* **C** (``--chips 4``) -- ``s4`` across four chips at ``levels=4``, and
  four ``TenantBatcher`` tenants at the Table II size merged into shared
  waves, each against the single-device ``s3`` result.  Both drain one
  bucket per shard (see ``phase_c``).

Every phase checks: results equal (``assert_array_equal``; where the chip
gives ulp-level differences, within the repo's documented 1e-5 relative
envelope, and the measured difference is printed), no NaN, relative mass
drift <= 1e-5, a positive shock radius, and all-zero fault counters in
every aggregation region.  Lines before the last are informational
(compile seconds, ms per step, launches per step, peak HBM bytes); the
last line is one JSON object naming the device.  With no TPU the script
exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

STEPS = 3
ENVELOPE = 1e-5          # DESIGN.md §15: the documented ulp envelope
MASS_DRIFT = 1e-5


def require_tpu():
    """The devices JAX sees, or exit non-zero when none is a TPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform "
                 f"{devs[0].platform!r}); this smoke test runs on the chip "
                 f"only")
    return devs


def peak_hbm_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def compare(name: str, out, ref) -> str:
    """Exact equality, or a deviation within the documented envelope
    (max |out - ref| over max |ref|); fails beyond it."""
    import numpy as np
    out, ref = np.asarray(out), np.asarray(ref)
    assert out.shape == ref.shape, (name, out.shape, ref.shape)
    if np.array_equal(out, ref):
        return "exact"
    dev = float(np.max(np.abs(out - ref))) / (float(np.max(np.abs(ref)))
                                               or 1.0)
    assert dev <= ENVELOPE, \
        f"{name}: deviates {dev:.3e} (rel) from its reference, beyond the " \
        f"{ENVELOPE:g} envelope"
    return f"within envelope, max rel deviation {dev:.3e}"


def check_physics(name: str, cfg, u0, u) -> None:
    import jax.numpy as jnp
    from repro.hydro.stepper import shock_radius, total_conserved
    assert not bool(jnp.any(jnp.isnan(u))), f"{name}: solution went NaN"
    h = cfg.domain / u.shape[-1]
    m0 = float(total_conserved(u0, h)[0])
    m1 = float(total_conserved(u, h)[0])
    drift = abs(m1 - m0) / m0
    assert drift <= MASS_DRIFT, f"{name}: mass drift {drift:.3e}"
    r = float(shock_radius(u, cfg))
    assert r > 0.0, f"{name}: shock radius {r}"


def check_faults(name: str, runner) -> None:
    """No rung banned, no retry, no wave quietly re-drained at bucket 1."""
    for fam, stats in runner.stats.get("regions", {}).items():
        faults = stats.get("faults", {})
        bad = {k: v for k, v in faults.items() if v}
        assert not bad, f"{name}: region {fam} recorded faults {bad}"


def run_strategy(name: str, cfg, agg, u0, dt, device):
    """Warm up, then time ``STEPS`` RK3 steps from ``u0``; returns the
    final state after checking it."""
    import jax
    from repro.core import StrategyRunner, UniformSedovScenario
    runner = StrategyRunner(UniformSedovScenario(cfg), agg)
    t0 = time.perf_counter()
    runner.warmup(wave_only=True)
    jax.block_until_ready(runner.rk3_step(u0, dt))
    compile_s = time.perf_counter() - t0
    launches0 = runner.stats["kernel_launches"]
    t0 = time.perf_counter()
    u = u0
    for _ in range(STEPS):
        u = runner.rk3_step(u, dt)
    jax.block_until_ready(u)
    ms = (time.perf_counter() - t0) * 1e3 / STEPS
    launches = (runner.stats["kernel_launches"] - launches0) / STEPS
    check_physics(name, cfg, u0, u)
    check_faults(name, runner)
    print(f"  {name}: compile+warm {compile_s:.2f} s, {ms:.2f} ms/step, "
          f"{launches:g} launches/step, peak HBM "
          f"{peak_hbm_bytes(device)} B")
    return u


def initial_state(cfg):
    from repro.hydro.state import sedov_init
    from repro.hydro.stepper import courant_dt
    u0 = sedov_init(cfg).u
    return u0, courant_dt(u0, cfg)


def phase_a(cfg, device) -> None:
    """Every strategy at the Table II size equals the fused result."""
    from repro.configs.base import AggregationConfig
    print(f"phase A: {cfg.n_subgrids} sub-grids of {cfg.subgrid}^3 "
          f"({cfg.cells_total} cells), {STEPS} RK3 steps")
    u0, dt = initial_state(cfg)
    ref = run_strategy("fused", cfg, AggregationConfig(strategy="fused"),
                       u0, dt, device)
    for strategy in ("s3", "mixed"):
        out = run_strategy(strategy, cfg,
                           AggregationConfig(strategy=strategy), u0, dt,
                           device)
        print(f"  {strategy} vs fused: {compare(strategy, out, ref)}")


def phase_b(cfg, device) -> None:
    """s3 at levels=4 against a chunked one-launch-per-wave reference; the
    whole-wave fused program either equals s3 or is refused for HBM."""
    import jax
    from repro.configs.base import AggregationConfig
    print(f"phase B: {cfg.n_subgrids} sub-grids of {cfg.subgrid}^3 "
          f"({cfg.cells_total} cells), {STEPS} RK3 steps")
    u0, dt = initial_state(cfg)
    out = run_strategy("s3", cfg, AggregationConfig(strategy="s3"), u0,
                       dt, device)
    n = cfg.n_subgrids
    chunked = AggregationConfig(strategy="s3", max_aggregated=n,
                                buckets=(1, n), inner_chunk=32)
    ref = run_strategy("s3 one wave in chunks of 32", cfg, chunked, u0,
                       dt, device)
    print(f"  s3 vs chunked reference: {compare('s3', out, ref)}")
    del ref
    from repro.core import StrategyRunner, UniformSedovScenario
    runner = StrategyRunner(UniformSedovScenario(cfg),
                            AggregationConfig(strategy="fused"))
    try:
        whole = u0
        for _ in range(STEPS):
            whole = runner.rk3_step(whole, dt)
        jax.block_until_ready(whole)
    except jax.errors.JaxRuntimeError as err:
        assert "RESOURCE_EXHAUSTED" in str(err), err
        print(f"  fused whole wave: refused for memory: "
              f"{str(err).splitlines()[0][:160]}")
    else:
        print(f"  fused whole wave: ran; vs s3: "
              f"{compare('fused', whole, out)}")


def phase_c(cfg_shard, cfg_tenant, devices) -> None:
    """s4 across four chips and a four-tenant merged step, each against
    the single-device s3 result."""
    import jax
    from repro.configs.base import AggregationConfig
    from repro.core import (ShardedAggregationExecutor, TenantBatcher,
                            UniformSedovScenario)
    from repro.distributed.api import subgrid_mesh
    n_dev = len(devices)
    print(f"phase C: s4 over {n_dev} chips, {cfg_shard.n_subgrids} "
          f"sub-grids; {n_dev} tenants of {cfg_tenant.n_subgrids} sub-grids")
    u0, dt = initial_state(cfg_shard)
    ref = run_strategy("s3 (one chip)", cfg_shard,
                       AggregationConfig(strategy="s3"), u0, dt,
                       devices[0])
    # one bucket per shard: s4 unrolls each shard's greedy drain into one
    # program; compiled for a v5e:2x2, 32 unrolled bucket-32 bodies took
    # 423 s on a CPU host, one 1,024-task body ~20 s
    local = cfg_shard.n_subgrids // n_dev
    out = run_strategy(
        "s4", cfg_shard, AggregationConfig(strategy="s4", shard_devices=n_dev,
                                           max_aggregated=local),
        u0, dt, devices[0])
    placed = len(out.sharding.device_set)
    assert placed == n_dev, f"s4 output on {placed} devices, not {n_dev}"
    print(f"  s4 vs s3: {compare('s4', out, ref)}; output on {placed} "
          f"devices")

    t0_u, t0_dt = initial_state(cfg_tenant)
    solo = run_strategy("s3 tenant (one chip)", cfg_tenant,
                        AggregationConfig(strategy="s3"), t0_u, t0_dt,
                        devices[0])
    exe = ShardedAggregationExecutor(
        config=AggregationConfig(strategy="s4",
                                 max_aggregated=cfg_tenant.n_subgrids),
        mesh=subgrid_mesh(n_dev), name="tenancy")
    tb = TenantBatcher(exe)
    for tid in range(n_dev):
        tb.add(tid, UniformSedovScenario(cfg_tenant), t0_u, t0_dt)
    t0 = time.perf_counter()
    for _ in range(STEPS):
        merged = tb.rk3_step_all()
    jax.block_until_ready(merged)
    wall = time.perf_counter() - t0
    occ = exe.stats["shard_occupancy"]
    assert len(occ) == n_dev and len(set(occ)) == 1 and occ[0] > 0, occ
    for tid in range(n_dev):
        print(f"  tenant {tid} vs solo s3: "
              f"{compare(f'tenant {tid}', merged[tid], solo)}")
    print(f"  tenancy: {STEPS} merged steps in {wall:.2f} s (compile "
          f"included), shard occupancy {occ}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs the sharded phase C only")
    args = ap.parse_args()

    devices = require_tpu()
    import jax
    from repro.configs.base import HydroConfig
    from repro.configs.sedov import CONFIG
    from repro.core.compile_cache import enable_compile_cache

    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX sees {len(devices)}")
    devices = devices[:args.chips]
    cache = enable_compile_cache()
    dev = devices[0]
    print(f"device: {dev.device_kind} x{len(devices)} "
          f"(platform {dev.platform}), jax {jax.__version__}, compile "
          f"cache {cache}")
    levels4 = HydroConfig(subgrid=8, ghost=3, levels=4)
    t0 = time.perf_counter()
    if args.chips == 1:
        phase_a(CONFIG, dev)
        phase_b(levels4, dev)
    else:
        phase_c(levels4, CONFIG, devices)
    print(f"total {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
