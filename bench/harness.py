"""One benchmark run: one cell, one seed, one measured window.

A cell is found by its name in ``BENCHMARK.json``, and everything it needs
by the names there, so a new configuration, traffic mix or metric is a new
file and no edit:

* ``configs[].file``              the configuration: scenario kind, sizes,
                                  initial-state noise, episode, checked and
                                  traced step counts, counts file and the
                                  limits of ``correct``;
* ``bench/traffic/<traffic>.json`` the traffic: the strategy that serves
                                  the steps;
* ``bench/scenarios/<kind>.py``   the system under test (``Program``);
* ``bench/reference/<kind>.py``   its plain reference, which imports
                                  nothing of the program;
* ``bench/metrics/<metric>.py``   one reader per metric, ``read(run)``,
                                  returning a number or ``None`` when it
                                  finds nothing to read.

A run makes the initial state from ``--seed``, warms up every program the
window runs, then steps as a user's loop does (Courant dt, RK3 step, one
host read of dt per step) for ``--seconds`` and closes the window with
``block_until_ready``.  A seeded reservoir keeps a few of the window's
steps; once the window has closed and the program is freed, the reference
recomputes each kept step from the same input state and the run is
``correct`` when every compared number is within its limit.
"""
from __future__ import annotations

import functools
import gc
import glob
import importlib.util
import json
import os
import random
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Optional

import roofline
import trace_reduce

BENCH = os.path.dirname(os.path.abspath(__file__))
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_retrieval_time_sec")


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """The module at ``path``, loaded once per process under ``name``."""
    mod = sys.modules.get(name)
    if mod is not None and getattr(mod, "__file__", None) == path:
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    sys.modules[name] = mod
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    bench: str

    def module(self, kind: str):
        """``scenarios`` or ``reference`` module of the config's kind."""
        scen = self.config["scenario"]
        return load_module(os.path.join(self.bench, kind, scen + ".py"),
                           f"bench_{kind}_{scen}")


def load_cell(root: str, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json``, with its files."""
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = os.path.join(root, "bench")

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return Cell(name=name, chips=w["chips"],
                config=load_json(os.path.join(root,
                                              configs[w["config"]]["file"])),
                traffic=load_json(os.path.join(bench, "traffic",
                                               w["traffic"] + ".json")),
                end_to_end=mine(spec["end_to_end"]),
                per_layer=mine(spec["per_layer"]), bench=bench)


@dataclass
class Run:
    """What a run measured; the metric readers read it."""
    cell: Cell
    device_kind: str
    setup_s: float = 0.0
    steps: int = 0
    window_s: float = 0.0
    host_s: list = field(default_factory=list)   # per rk3_step call
    launches: int = 0
    staging_s: Optional[float] = None
    trace: Optional[dict] = None
    reference: object = None

    def least_step_s(self) -> float:
        bench = self.cell.bench
        return roofline.least_step_s(self.cell.config, self.reference,
                                     roofline.peaks(self.device_kind, bench),
                                     bench)


class CompileCounter:
    """Counts traces, compiles and compile-cache reads while armed."""

    def __init__(self):
        import jax.monitoring
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


def window(prog, u0, seconds: float, seed: int, config: dict, run: Run):
    """Step as the user's loop does until ``seconds`` have passed, starting
    a new simulation from ``u0`` every ``config["episode_steps"]`` steps;
    returns the final state and a seeded reservoir of
    ``config["checked_steps"]`` steps, each ``(k, u_k, dt_k, u_k+1)``."""
    import jax
    rng = random.Random(seed)
    keep, episode = config["checked_steps"], config["episode_steps"]
    kept, host, c0 = [], [], prog.counters()
    k, t0 = 0, time.perf_counter()
    while True:
        if k % episode == 0:
            u = u0
        dt = prog.dt(u)
        a = time.perf_counter()
        u1 = prog.step(u, dt)
        host.append(time.perf_counter() - a)
        float(dt)                       # the user's per-step host read
        item = (k, u, dt, u1)
        if k < keep:
            kept.append(item)
        elif (j := rng.randrange(k + 1)) < keep:
            kept[j] = item
        u, k = u1, k + 1
        if time.perf_counter() - t0 >= seconds:
            break
    jax.block_until_ready(u)
    run.window_s = time.perf_counter() - t0
    c1 = prog.counters()
    run.steps, run.host_s = k, host
    run.launches = c1["launches"] - c0["launches"]
    if c1["staging_s"] is not None:
        run.staging_s = c1["staging_s"] - c0["staging_s"]
    return u, sorted(kept, key=lambda item: item[0])


def traced_steps(prog, u, n: int, save_to: Optional[str] = None):
    """``n`` more steps under the profiler, each host call in a named span;
    returns the reduced trace (``trace_reduce.reduce_trace``), and keeps
    the raw trace at ``save_to`` where given."""
    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=options)
        with TraceAnnotation("bench_window"):
            for _ in range(n):
                with TraceAnnotation("courant_dt"):
                    dt = prog.dt(u)
                with TraceAnnotation("rk3_step"):
                    u = prog.step(u, dt)
                with TraceAnnotation("dt_sync"):
                    float(dt)
            jax.block_until_ready(u)
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        if save_to:
            shutil.copy(path, save_to)
        return trace_reduce.reduce_trace(ProfileData.from_file(path))


F32_SPACING = 2.0 ** -23


def _gaps(u, out, dt, ref, dt_ref, magnitude):
    """The largest gap between the program's next state and the
    reference's, over every cell and field, in float32 spacings of that
    cell's own magnitude (``magnitude``, as the reference defines it, of
    the input or of the reference's output, whichever is larger); the
    relative gap between the two dts; the non-finite values in ``out`` and
    ``ref`` (a device's max reduction need not propagate NaN)."""
    import jax.numpy as jnp
    u, out, ref = (x.astype(jnp.float32) for x in (u, out, ref))
    unit = F32_SPACING * jnp.maximum(magnitude(u), magnitude(ref))
    unit = jnp.maximum(unit, jnp.finfo(jnp.float32).tiny)
    dt, dt_ref = dt.astype(jnp.float32), dt_ref.astype(jnp.float32)
    return (jnp.max(jnp.abs(out - ref) / unit),
            jnp.abs(dt - dt_ref) / jnp.abs(dt_ref),
            jnp.sum(~jnp.isfinite(out)) + jnp.sum(~jnp.isfinite(ref)))


@functools.cache
def _gaps_jit(reference, hydro: str):
    import jax
    magnitude = functools.partial(reference.magnitude,
                                  hydro=json.loads(hydro))
    return jax.jit(functools.partial(_gaps, magnitude=magnitude))


def compare_steps(reference, config: dict, kept, control_dtype=None):
    """Each kept step against the reference step from the same input.
    With ``control_dtype`` the reference computed in that dtype takes the
    program's place (the control).  Returns per-step
    ``(step_ulps, dt_gap, nonfinite)``."""
    step = reference.make_step(config)
    gaps = _gaps_jit(reference, json.dumps(config["hydro"], sort_keys=True))
    out = []
    for _, u, dt, u1 in kept:
        dt_r, u_r = step(u)
        if control_dtype is not None:
            dt, u1 = step(u.astype(control_dtype))
        out.append(tuple(float(x) for x in gaps(u, u1, dt, u_r, dt_r)))
    return out


def _worst(values):
    """Largest value, a NaN ranking above every number."""
    return max(values, key=lambda v: (v != v, v))


def judge(per_step, nonfinite_final: int, faults: int, limits: dict):
    """Compared numbers with their limits, and how many checks failed."""
    worst = {"step_ulps": _worst(s[0] for s in per_step),
             "dt_gap": _worst(s[1] for s in per_step),
             "nonfinite": int(sum(s[2] for s in per_step) + nonfinite_final),
             "faults": int(faults)}
    failed = sum(1 for s in per_step
                 if not (s[0] <= limits["step_ulps"]
                         and s[1] <= limits["dt_gap"] and s[2] == 0))
    failed += int(nonfinite_final > 0) + int(faults > 0)
    compared = {k: {"value": v if v == v and abs(v) != float("inf")
                    else None, "limit": limits[k]}
                for k, v in worst.items()}
    return compared, failed


def read_metrics(run: Run, metrics: list) -> dict:
    out = {}
    for m in metrics:
        reader = load_module(os.path.join(run.cell.bench, "metrics",
                                          m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(root: str, name: str, seed: int, seconds: float, trace: bool,
             t_start: float, require_chip: bool = True, log=print) -> dict:
    """One run of cell ``name``; returns the result line's object."""
    cell = load_cell(root, name)
    import jax
    import jax.numpy as jnp
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is "
                         f"{devices[0].platform!r}; the benchmark runs on "
                         f"the chip only")
    if len(devices) < cell.chips:
        raise SystemExit(f"{name} needs {cell.chips} chips, JAX sees "
                         f"{len(devices)}")
    devices = devices[:cell.chips]
    counter = CompileCounter()
    scen, reference = cell.module("scenarios"), cell.module("reference")
    run = Run(cell=cell, device_kind=devices[0].device_kind,
              reference=reference)

    prog = scen.Program(cell.config, cell.traffic)
    u0 = reference.initial_state(cell.config, seed)
    prog.warmup(u0)
    run.setup_s = time.perf_counter() - t_start

    counter.armed = True
    u, kept = window(prog, u0, seconds, seed, cell.config, run)
    counter.armed = False
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    if trace:
        run.trace = traced_steps(prog, u0, cell.config["trace_steps"])
    nonfinite_final = int(jnp.sum(~jnp.isfinite(u)))
    faults = prog.faults()
    del prog, u
    gc.collect()

    per_step = compare_steps(reference, cell.config, kept)
    compared, failed = judge(per_step, nonfinite_final, faults,
                             cell.config["limits"])
    log(f"{name}: seed {seed}, setup {run.setup_s:.3f} s, {run.steps} "
        f"steps in {run.window_s:.3f} s, {run.launches} launches, "
        f"compiles in window {counter.count}, checked steps "
        f"{[item[0] for item in kept]}")
    metrics = read_metrics(run, cell.per_layer if trace else cell.end_to_end)
    device = {"platform": devices[0].platform, "kind": run.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    result = {"correct": failed == 0, "attempted": run.steps,
              "failed": failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {k: run.trace[k]
                               for k in ("device_ops", "idle_gaps")}
    result["compared"] = compared
    return result


def enable_cache(root: str) -> None:
    """Put the program on ``root``'s ``src`` and its persistent compile
    cache at ``root/.jax_cache``, whatever the environment names, so that
    two checkouts share no compiled programs."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    sys.path.insert(0, os.path.join(root, "src"))
    from repro.core.compile_cache import enable_compile_cache
    enable_compile_cache()


def main(argv=None, t_start: Optional[float] = None) -> None:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(BENCH)
    enable_cache(root)
    result = run_cell(root, args.workload, args.seed, args.seconds,
                      bool(args.trace), t_start)
    for k, v in result["compared"].items():
        print(f"{k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
