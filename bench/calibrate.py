"""Readings for the limits of ``correct``, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <run_seconds> [--save-trace PATH]

Builds the cell's program once, then for each seed makes that seed's
initial state, runs a window of ``--seconds`` exactly as ``run.py`` does
and prints, per seed, the program's compared numbers (the lower
readings).  For each control seed it also prints the numbers of the
control: the plain reference computed in bfloat16 put in the program's
place at the same kept steps (the upper readings).  The benchmark's own
runs never run the control.  ``--save-trace`` keeps the profiler trace of
``trace_steps`` steps at PATH and prints the trace's planes and lines.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import harness


def main() -> None:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save-trace", default="")
    args = ap.parse_args()
    root = os.path.dirname(harness.BENCH)
    harness.enable_cache(root)
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate runs on the chip only")
    cell = harness.load_cell(root, args.workload)
    scen, reference = cell.module("scenarios"), cell.module("reference")
    prog = scen.Program(cell.config, cell.traffic)
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell=cell, device_kind="")
        u0 = reference.initial_state(cell.config, seed)
        if i == 0:
            prog.warmup(u0)
            print(f"setup {time.perf_counter() - t_start:.3f} s", flush=True)
        u, kept = harness.window(prog, u0, args.seconds, seed,
                                 cell.config, run)
        rows = {"program": harness.compare_steps(reference, cell.config,
                                                 kept)}
        if seed in controls:
            rows["control"] = harness.compare_steps(
                reference, cell.config, kept, jnp.bfloat16)
        for side, per_step in rows.items():
            compared, failed = harness.judge(
                per_step, int(jnp.sum(~jnp.isfinite(u))), prog.faults(),
                cell.config["limits"])
            print(json.dumps({"seed": seed, "side": side,
                              "steps": run.steps,
                              "ms_per_step": run.window_s * 1e3 / run.steps,
                              "checked": [item[0] for item in kept],
                              "per_step": per_step, "failed": failed,
                              "compared": compared}), flush=True)
    if args.save_trace:
        save_trace(prog, u0, cell.config["trace_steps"], args.save_trace)


def save_trace(prog, u, n: int, dest: str) -> None:
    """Keep a trace of ``n`` steps at ``dest`` and print its planes and
    lines, and what ``trace_reduce`` makes of it."""
    from jax.profiler import ProfileData
    os.makedirs(os.path.dirname(os.path.abspath(dest)), exist_ok=True)
    reduced = harness.traced_steps(prog, u, n, save_to=dest)
    for plane in ProfileData.from_file(dest).planes:
        for line in plane.lines:
            evs = list(line.events)
            if evs:
                print(json.dumps({
                    "plane": plane.name, "line": line.name,
                    "events": len(evs), "first_ns": evs[0].start_ns,
                    "last_end_ns": max(e.end_ns for e in evs),
                    "names": sorted({e.name for e in evs})[:12]}))
    print("reduced", json.dumps(reduced))
    print(f"trace bytes {os.path.getsize(dest)}")


if __name__ == "__main__":
    main()
