"""Chaos soak: randomized multi-site fault schedules across kill/restart.

The DESIGN.md §14 acceptance harness.  One seeded run drives every
survivability layer this repo claims, end to end:

* **wave containment** — a wave with randomly chosen poisoned tasks must
  fail EXACTLY the culprits; every survivor's result is bit-identical to
  the fault-free reference (the §11 invariant, re-checked under chaos);
* **circuit breaker** — a fault burst on one family must trip its breaker
  (``open``), and the breaker must RECOVER (``half_open`` probe →
  ``closed``) within the run, with the bucket-1 floor observable while
  open;
* **kill + crash-consistent resume** — a child process runs the s3
  trajectory under a saved fault schedule (transient launch failures, a
  hang the watchdog must end, a bucket-compile fault) with per-step
  checkpoints, and is SIGKILLed mid-run; the parent resumes from the
  latest checkpoint (replaying the same schedule) and the final state
  must be bit-identical to an uninterrupted fault-free run, with
  ``warm_start=true`` on the resumed process (tune store + checkpoint
  travel together);
* **serving degradation** — deadlines shed stale requests, ``max_pending``
  backpressure rejects overload with ``EngineOverloaded``, a poisoned
  tenant is evicted, and ``healthz()`` reports it all;
* **watchdog overhead** — the flush-time launch watchdog
  (``launch_timeout_s``) must cost <= 5% vs the unwatched twin, measured
  PAIRED (back-to-back per-repeat ratios, median — bench_util).

Emits BENCH_chaos_soak.json with one ``s3_chaos_soak`` row (merged fault
counters: ``timeouts`` / ``shed`` / ``breaker_trips`` / ..., the
``recovery_steps`` the resume replayed, final breaker states, the serving
``healthz`` snapshot) plus the paired ``s3_watchdog_overhead`` row — both
gated by benchmarks/check_bench_schema.py.

  PYTHONPATH=src python benchmarks/chaos_soak.py [--smoke] [--seed N]

``--child`` is internal: the kill-target subprocess re-enters this file.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import shutil
import signal
import subprocess
import sys
import time
from typing import List

import jax
import jax.numpy as jnp
import numpy as np
from bench_util import WM, paired_overhead_pct, region_breakers, warm_start

from repro.checkpoint.ckpt import latest_step
from repro.configs.base import AggregationConfig, HydroConfig
from repro.core import (
    AggregationExecutor, FaultInjector, FaultSpec, StrategyRunner,
    UniformSedovScenario,
)
from repro.hydro.state import sedov_init
from repro.hydro.stepper import courant_dt

OUT_PATH = os.path.join(os.path.dirname(__file__), os.pardir,
                        "BENCH_chaos_soak.json")
SRC_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "src")
KERNEL = "hydro_rhs"          # the Sedov scenario's one kernel family


def _body(x):
    return x * 2.0 + 1.0


def _chaos_agg(cfg: HydroConfig, store: str) -> AggregationConfig:
    """The trajectory config under test: every §14 defence armed at once
    (watchdog, breaker, bounded capped retries, guard, persistent tuning).
    ``cost_model`` + ``tune_store`` make the post-crash resume warm-start
    provable (the child's per-checkpoint ``save_tuning`` feeds it)."""
    return AggregationConfig(
        strategy="s3", n_executors=1, staging="device",
        max_aggregated=cfg.n_subgrids, launch_watermark=WM, autotune=True,
        cost_model=True, guard="finite", launch_timeout_s=0.5,
        retry_backoff_s=0.001, retry_backoff_max_s=0.01,
        breaker_window=16, breaker_threshold=3, breaker_cooldown=2,
        tune_store=store)


def build_schedule(seed: int, kernel: str = KERNEL) -> List[FaultSpec]:
    """Randomized multi-site schedule, every fault SURVIVABLE by design
    (launch-site + compile-site only — payload poison is exercised by the
    wave segment, where per-task failure is the expected outcome)."""
    rng = random.Random(seed)
    specs = [
        # one hang: only the watchdog can end it (LaunchTimeoutError ->
        # retry), and it must land in the 'timeouts' counter
        FaultSpec(site="launch", kernel=kernel, mode="hang", times=1),
        # one compile fault on a random drain-reachable rung: bans the
        # rung, the wave degrades through smaller buckets
        FaultSpec(site="compile", kernel=kernel,
                  bucket=rng.choice((2, 4)), times=1),
    ]
    for _ in range(rng.randint(1, 2)):   # transient launch failures
        specs.append(FaultSpec(site="launch", kernel=kernel, mode="fail",
                               times=rng.randint(1, 2)))
    return specs


# ---------------------------------------------------------------------------
# segment 1: wave containment under random payload poison
# ---------------------------------------------------------------------------

def wave_segment(seed: int) -> dict:
    rng = random.Random(seed + 1)
    n = 32
    culprits = sorted(rng.sample(range(n), 3))
    specs = [FaultSpec(site="payload", kernel="k", task=c,
                       mode=rng.choice(("nan", "inf")), times=1)
             for c in culprits]
    cfg = AggregationConfig(max_aggregated=n, guard="finite",
                            launch_timeout_s=0.5)
    exe = AggregationExecutor(None, cfg,
                              fault_injector=FaultInjector(specs, seed=seed))
    exe.register("k", _body)
    parents = (jnp.arange(n, dtype=jnp.float32).reshape(n, 1) * 0.5,)
    fut = exe.submit_range(parents, 0, n, kernel="k")
    exe.flush()
    ref = np.asarray(_body(parents[0]))
    assert sorted(fut.failed_indices()) == culprits, \
        f"wave segment: failed {fut.failed_indices()} != injected {culprits}"
    for i in range(n):
        if i not in culprits:
            np.testing.assert_array_equal(np.asarray(fut.task_result(i)),
                                          ref[i])
    print(f"  wave: {len(culprits)} culprits isolated, "
          f"{n - len(culprits)} survivors bit-identical")
    return {f"wave:{fam}": dict(s["faults"])
            for fam, s in exe.stats["regions"].items()}


# ---------------------------------------------------------------------------
# segment 2: breaker trips on a fault burst, then recovers
# ---------------------------------------------------------------------------

def breaker_segment(seed: int) -> dict:
    cfg = AggregationConfig(max_aggregated=8, guard="finite",
                            breaker_window=4, breaker_threshold=2,
                            breaker_cooldown=2)
    specs = [FaultSpec(site="payload", kernel="k", task=1, mode="nan",
                       times=3)]
    exe = AggregationExecutor(None, cfg,
                              fault_injector=FaultInjector(specs, seed=seed))
    exe.register("k", _body)
    parents = (jnp.arange(8, dtype=jnp.float32).reshape(8, 1) * 0.5,)
    trace, floor_seen = [], False
    for _ in range(10):
        exe.submit_range(parents, 0, 8, kernel="k")
        exe.flush()
        reg = exe.stats["regions"]["k[1]"]
        trace.append(reg["breaker"])
        if reg["breaker"] == "open":
            # the open-state floor: the NEXT wave may not launch above
            # bucket 1 (checked via the executor's own bucket picker)
            floor_seen = True
            assert exe._largest_bucket(exe._primary_region("k"), 8) == 1
    faults = dict(exe.stats["regions"]["k[1]"]["faults"])
    assert {"open", "half_open", "closed"} <= set(trace), \
        f"breaker never completed its lifecycle: {trace}"
    assert trace[-1] == "closed", f"breaker did not recover: {trace}"
    assert floor_seen and faults["breaker_trips"] >= 1
    print(f"  breaker: trace {'>'.join(dict.fromkeys(trace))} "
          f"(trips={faults['breaker_trips']}, bucket-1 floor held)")
    return {"breaker:k[1]": faults}


# ---------------------------------------------------------------------------
# segment 3: kill mid-run (first, before JAX), resume crash-consistently
# ---------------------------------------------------------------------------

def child_run(workdir: str, levels: int, n_steps: int, kill_at: int,
              checkpoint_every: int) -> None:
    """The kill target: runs the chaos trajectory with per-step
    checkpoints and SIGKILLs ITSELF right after publishing checkpoint
    ``kill_at`` — a crash with a durable prefix on disk."""
    cfg = HydroConfig(subgrid=8, ghost=3, levels=levels)
    st = sedov_init(cfg)
    dt = courant_dt(st.u, cfg)
    inj = FaultInjector.from_schedule(os.path.join(workdir, "schedule.json"))
    runner = StrategyRunner(
        UniformSedovScenario(cfg),
        _chaos_agg(cfg, os.path.join(workdir, "tunestore")),
        fault_injector=inj)
    orig = runner._checkpoint

    def checkpoint_then_die(ckpt_dir, step, *args, **kw):
        orig(ckpt_dir, step, *args, **kw)
        if step >= kill_at:
            os.kill(os.getpid(), signal.SIGKILL)

    runner._checkpoint = checkpoint_then_die
    runner.run(st.u, dt, n_steps, checkpoint_every=checkpoint_every,
               ckpt_dir=os.path.join(workdir, "ckpt"))
    raise SystemExit("chaos child survived past kill_at — kill never fired")


N_STEPS, KILL_AT = 4, 2      # the kill target's trajectory and kill point


def kill_segment(workdir: str, levels: int, seed: int) -> str:
    """Run the kill target in a child process and check that it died with
    checkpoint ``KILL_AT`` on disk; returns the saved fault schedule.

    Runs before this process touches JAX: an accelerator belongs to one
    process at a time, and a parent that had initialized a backend would
    hold the chip the child needs."""
    # a previous soak's resumed checkpoints would shadow this run's kill
    # point (latest_step takes the max) — every soak starts from scratch
    shutil.rmtree(os.path.join(workdir, "ckpt"), ignore_errors=True)
    os.makedirs(workdir, exist_ok=True)
    inj = FaultInjector(build_schedule(seed), seed=seed)
    schedule = inj.save_schedule(os.path.join(workdir, "schedule.json"))

    env = dict(os.environ)
    env["PYTHONPATH"] = SRC_PATH + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", workdir,
         "--levels", str(levels), "--n-steps", str(N_STEPS),
         "--kill-at", str(KILL_AT), "--checkpoint-every", "1"],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0, \
        f"chaos child exited cleanly — the mid-run kill never fired:\n" \
        f"{proc.stdout}\n{proc.stderr}"
    ckpt_dir = os.path.join(workdir, "ckpt")
    assert latest_step(ckpt_dir) == KILL_AT, \
        f"expected checkpoint {KILL_AT}, found {latest_step(ckpt_dir)}:\n" \
        f"{proc.stdout}\n{proc.stderr}"
    return schedule


def resume_segment(workdir: str, levels: int, schedule: str) -> dict:
    cfg = HydroConfig(subgrid=8, ghost=3, levels=levels)
    st = sedov_init(cfg)
    dt = courant_dt(st.u, cfg)
    ckpt_dir = os.path.join(workdir, "ckpt")

    # resume in THIS process, replaying the same fault schedule (faults
    # fire on both sides of the crash; all of them are survivable)
    runner = StrategyRunner(
        UniformSedovScenario(cfg),
        _chaos_agg(cfg, os.path.join(workdir, "tunestore")),
        fault_injector=FaultInjector.from_schedule(schedule))
    t0 = time.perf_counter()
    final = runner.resume(ckpt_dir, st.u)
    jax.block_until_ready(final)
    wall = time.perf_counter() - t0
    recovery = int(runner.stats["recovery_steps"])
    assert recovery == N_STEPS - KILL_AT
    assert warm_start(runner), \
        "resumed process did not warm-start from the tune store"

    # the acceptance bar: the killed-and-resumed trajectory is
    # BIT-IDENTICAL to one uninterrupted fault-free run
    ref_runner = StrategyRunner(
        UniformSedovScenario(cfg),
        AggregationConfig(strategy="s3", n_executors=1, staging="device",
                          max_aggregated=cfg.n_subgrids,
                          launch_watermark=WM))
    ref = ref_runner.run(st.u, dt, N_STEPS)
    np.testing.assert_array_equal(np.asarray(final), np.asarray(ref))
    exe_stats = runner.executor.stats
    fam_faults = {f"trajectory:{fam}": dict(s["faults"])
                  for fam, s in exe_stats["regions"].items()}
    timeouts = sum(f["timeouts"] for f in fam_faults.values())
    print(f"  kill+resume: killed at step {KILL_AT}/{N_STEPS}, resumed "
          f"{recovery} steps (warm start), bit-identical to uninterrupted "
          f"run; watchdog timeouts={timeouts}")
    return {
        "faults": fam_faults,
        "breakers": {f"trajectory:{fam}": state
                     for fam, state in region_breakers(runner).items()},
        "recovery_steps": recovery,
        "ms_per_step": round(wall * 1e3 / max(1, recovery), 3),
        "launches_per_step": exe_stats["launches"] / max(1, recovery),
    }


# ---------------------------------------------------------------------------
# segment 4: serving deadlines, backpressure, eviction, healthz
# ---------------------------------------------------------------------------

def serving_segment(seed: int) -> dict:
    from repro.configs import get_config, reduced
    from repro.models import model as model_mod
    from repro.serving import EngineOverloaded, Request, ServingEngine

    cfg = reduced(get_config("granite-8b"))
    params = model_mod.init_params(cfg, jax.random.PRNGKey(0))
    inj = FaultInjector([FaultSpec(site="payload", kernel="decode", task=1,
                                   mode="nan", times=1)], seed=seed)
    eng = ServingEngine(cfg, params, max_batch=2, max_len=32, max_pending=2,
                        agg=AggregationConfig(max_aggregated=2,
                                              guard="finite"),
                        fault_injector=inj)
    overloaded = 0
    eng.submit(Request(0, [3, 5, 7], max_new_tokens=3))
    eng.submit(Request(1, [2, 4, 6], max_new_tokens=3))   # poisoned tenant
    try:
        eng.submit(Request(2, [1, 2], max_new_tokens=2))  # queue full
    except EngineOverloaded:
        overloaded += 1
    eng.run()
    # a request whose deadline expires while queued is shed, not served
    stale = Request(3, [1, 2], max_new_tokens=2, deadline_s=1e-4)
    eng.submit(stale)
    time.sleep(0.01)
    eng.run()
    health = eng.healthz()
    assert stale.failed and "shed" in stale.error
    assert health["evicted"] >= 1 and health["shed"] >= 1
    assert health["slots_free"] == 2 and health["queue_depth"] == 0
    eng.close()
    try:
        eng.submit(Request(4, [1], max_new_tokens=1))
    except EngineOverloaded:
        overloaded += 1
    assert overloaded == 2
    print(f"  serving: evicted={health['evicted']} shed={health['shed']} "
          f"overloaded={overloaded} (healthz consistent, closed engine "
          f"rejects)")
    stats = dict(eng.stats["faults"])
    stats["overloaded"] = overloaded
    return {"faults": {"serving": stats}, "healthz": health}


# ---------------------------------------------------------------------------
# segment 5: paired watchdog overhead (<= 5%)
# ---------------------------------------------------------------------------

def watchdog_overhead_segment(levels: int, steps: int, repeats: int) -> dict:
    cfg = HydroConfig(subgrid=8, ghost=3, levels=levels)
    st = sedov_init(cfg)
    dt = courant_dt(st.u, cfg)
    scn = UniformSedovScenario(cfg)
    runners = {}
    for name, timeout in (("off", 0.0), ("on", 5.0)):
        agg = AggregationConfig(strategy="s3", n_executors=1,
                                staging="device",
                                max_aggregated=cfg.n_subgrids,
                                launch_watermark=WM, autotune=True,
                                launch_timeout_s=timeout)
        r = StrategyRunner(scn, agg)
        r.warmup(wave_only=True)
        r.rk3_step(st.u, dt)                      # compile outside timing
        runners[name] = r
    pct, ratios = paired_overhead_pct(runners["off"].rk3_step,
                                      runners["on"].rk3_step, st.u, dt,
                                      max(2, steps), repeats)
    assert pct <= 5.0, \
        f"watchdog overhead {pct:+.2f}% exceeds the 5% acceptance bar " \
        f"(ratios={ratios})"
    from bench_util import time_per_step
    sec, samples = time_per_step(runners["on"].rk3_step, st.u, dt,
                                 max(2, steps), repeats)
    print(f"  watchdog: paired overhead {pct:+.2f}% (<= 5%), "
          f"ratios={ratios}")
    return {
        "config": "s3_watchdog_overhead", "strategy": "s3",
        "ms_per_step": round(sec * 1e3, 3),
        "launches_per_step":
            runners["on"].executor.stats["launches"] / max(1, steps),
        "ms_per_step_samples": [round(s * 1e3, 3) for s in samples],
        "watchdog_overhead_pct": pct,
        "watchdog_overhead_ratios": ratios,
    }


# ---------------------------------------------------------------------------

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale: smallest grid, 1 timing step/repeat")
    ap.add_argument("--seed", type=int, default=1234,
                    help="schedule seed (the whole soak is replayable)")
    ap.add_argument("--steps", type=int, default=2)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--workdir", default=".chaos-soak",
                    help="checkpoint/store/schedule scratch directory")
    ap.add_argument("--child", metavar="WORKDIR",
                    help="internal: run the kill-target trajectory")
    ap.add_argument("--levels", type=int, default=1)
    ap.add_argument("--n-steps", type=int, default=4)
    ap.add_argument("--kill-at", type=int, default=2)
    ap.add_argument("--checkpoint-every", type=int, default=1)
    args = ap.parse_args()
    if args.child:
        child_run(args.child, args.levels, args.n_steps, args.kill_at,
                  args.checkpoint_every)
        return
    if args.smoke:
        args.steps, args.repeats = 1, 1
    levels = args.levels
    workdir = os.path.abspath(args.workdir)
    schedule = kill_segment(workdir, levels, args.seed)
    print(f"chaos_soak: seed={args.seed}, backend={jax.default_backend()}")

    faults, extras = {}, {}
    faults.update(wave_segment(args.seed))
    faults.update(breaker_segment(args.seed))
    resume_info = resume_segment(workdir, levels, schedule)
    faults.update(resume_info["faults"])
    serving_info = serving_segment(args.seed)
    faults.update(serving_info["faults"])

    soak_row = {
        "config": "s3_chaos_soak", "strategy": "s3",
        "ms_per_step": resume_info["ms_per_step"],
        "launches_per_step": resume_info["launches_per_step"],
        "recovery_steps": resume_info["recovery_steps"],
        "breakers": resume_info["breakers"],
        "faults": faults,
        "healthz": serving_info["healthz"],
    }
    rows = [soak_row,
            watchdog_overhead_segment(levels, args.steps, args.repeats)]
    payload = {
        "benchmark": "chaos_soak",
        "backend": jax.default_backend(),
        "config": "sedov+serving",
        "seed": args.seed,
        "steps": args.steps,
        "repeats": args.repeats,
        "rows": rows,
    }
    with open(OUT_PATH, "w") as f:
        json.dump(payload, f, indent=2)
    print(f"wrote {os.path.abspath(OUT_PATH)}")


if __name__ == "__main__":
    main()
