import os
# a dry run compiles for 512 virtual host devices and never needs a chip:
# pin it to the CPU so that on a TPU host it leaves the chip to others
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. derives parameter/optimizer/batch/cache shardings from the logical
     rules (repro.launch.sharding),
  3. ``jit(step).lower(...).compile()`` against ShapeDtypeStructs — no
     allocation; success proves the distribution config is coherent,
  4. records ``memory_analysis()`` (fits-in-HBM proof), ``cost_analysis()``
     (FLOPs / bytes) and the collective-bytes breakdown parsed from the
     optimized HLO — the three roofline terms of EXPERIMENTS.md §Roofline.

Results are cached as JSON under ``benchmarks/results/`` so reruns only
compile missing cells.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch starcoder2-15b \
      --shape train_4k --mesh pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
"""
import argparse
import json
import re
import time
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES_BY_NAME, get_config, shape_applicable
from repro.configs.base import ShapeConfig
from repro.distributed.api import logical_rules
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import make_all_specs, named, rules_overrides
from repro.launch.steps import make_prefill_step, make_serve_step, make_train_step
from repro.launch.roofline import (
    parse_collectives_with_trips, roofline_terms,
)
from repro.optim.adamw import OptConfig, opt_init

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results")

def dryrun_cell(arch: str, shape_name: str, multi_pod: bool,
                verbose: bool = True) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = SHAPES_BY_NAME[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multipod" if multi_pod else "pod",
                "skipped": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()

    # Training-cell policy (EXPERIMENTS.md §Perf P1): Megatron-SP buys
    # activation memory but costs two activation all-gathers per layer per
    # pass (9.35 s collective vs 1.44 s compute for granite train under the
    # no-overlap model); gradient accumulation buys the same memory for 6x
    # fewer collective bytes.  MoE keeps SP — its dispatch needs both.
    microbatch = 0
    overrides: Dict[str, Any] = {}
    if shape.kind == "train":
        microbatch = 4
        if cfg.family != "moe":
            overrides["seq_sp"] = None

    (params_sh, batch_sh, cache_sh, pspec, ospec, bspec, cspec
     ) = make_all_specs(cfg, shape, mesh, overrides=overrides)

    opt_cfg = OptConfig()
    rules = dict(rules_overrides(shape, cfg))
    rules.update(overrides)
    # the logical-rules context must be live during tracing so that in-model
    # ``constrain`` calls resolve (keeps scan residuals sharded)
    with mesh, logical_rules(mesh, rules):
        if shape.kind == "train":
            step = make_train_step(cfg, opt_cfg, microbatch=microbatch)
            opt_sh = jax.eval_shape(opt_init, params_sh)
            rep = jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec())
            lowered = jax.jit(
                step,
                in_shardings=(named(mesh, pspec), named(mesh, ospec),
                              named(mesh, bspec)),
                out_shardings=(named(mesh, pspec), named(mesh, ospec),
                               {"loss": rep, "grad_norm": rep, "lr": rep}),
                donate_argnums=(0, 1),
            ).lower(params_sh, opt_sh, batch_sh)
        elif shape.kind == "prefill":
            step = make_prefill_step(cfg)
            lowered = jax.jit(
                step,
                in_shardings=(named(mesh, pspec), named(mesh, bspec)),
            ).lower(params_sh, batch_sh)
        else:  # decode
            step = make_serve_step(cfg)
            tok_sh = batch_sh
            lowered = jax.jit(
                step,
                in_shardings=(named(mesh, pspec), named(mesh, cspec),
                              named(mesh, bspec)),
                out_shardings=(None, named(mesh, cspec)),
                donate_argnums=(1,),
            ).lower(params_sh, cache_sh, tok_sh)

        compiled = lowered.compile()

    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    hlo = compiled.as_text()
    coll = parse_collectives_with_trips(hlo)

    mem_info: Dict[str, Any] = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            v = getattr(mem, attr, None)
            if v is not None:
                mem_info[attr] = int(v)
        live = (mem_info.get("argument_size_in_bytes", 0)
                + mem_info.get("output_size_in_bytes", 0)
                + mem_info.get("temp_size_in_bytes", 0)
                - mem_info.get("alias_size_in_bytes", 0))
        mem_info["peak_bytes_per_device_est"] = live

    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "multipod" if multi_pod else "pod",
        "chips": chips,
        "compile_s": round(compile_s, 1),
        "memory": mem_info,
        "roofline": roofline_terms(cfg, shape, chips, coll),
        # raw cost_analysis: CAVEAT — while-loop (scan) bodies are counted
        # once, so these under-report for scanned stacks; the roofline terms
        # above use the analytic model + trip-count-aware collective parse.
        "hlo_cost_analysis_raw": {
            "flops": float((cost or {}).get("flops", 0.0)),
            "bytes_accessed": float((cost or {}).get("bytes accessed", 0.0)),
        },
    }
    if verbose:
        print(json.dumps(result, indent=2))
        print(f"  memory_analysis: {mem}")
    return result


def result_path(arch: str, shape: str, mesh: str) -> str:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    return os.path.join(RESULTS_DIR, f"dryrun_{mesh}_{arch}_{shape}.json")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=("pod", "multipod", "both"),
                    default="pod")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = list(ARCHS) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES_BY_NAME) if (args.all or not args.shape) \
        else [args.shape]
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    failures = []
    for mesh_name in meshes:
        for arch in archs:
            for shape in shapes:
                path = result_path(arch, shape, mesh_name)
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {mesh_name} {arch} {shape}")
                    continue
                print(f"[dryrun] {mesh_name} {arch} {shape} ...", flush=True)
                try:
                    res = dryrun_cell(arch, shape,
                                      multi_pod=(mesh_name == "multipod"))
                except Exception as e:  # noqa: BLE001 — report, keep going
                    import traceback
                    traceback.print_exc()
                    failures.append((mesh_name, arch, shape, repr(e)))
                    continue
                with open(path, "w") as f:
                    json.dump(res, f, indent=2)
    if failures:
        print("FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("all requested cells compiled OK")


if __name__ == "__main__":
    main()
