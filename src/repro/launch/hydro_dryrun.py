import os
# a dry run compiles for 512 virtual host devices and never needs a chip:
# pin it to the CPU so that on a TPU host it leaves the chip to others
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Distributed dry-run of the paper's own scenario: the Sedov blast wave
sub-grids sharded across the production mesh.

Octo-Tiger distributes sub-grids across nodes via HPX parcels; here the
assembled grid's spatial axes shard over the DP mesh axes and the ghost
exchange (extract_subgrids) lowers to halo collectives inserted by XLA —
the distribution config of the hydro substrate is proven coherent the same
way the LM cells are.

  PYTHONPATH=src python -m repro.launch.hydro_dryrun [--multipod] [--levels 4]
"""
import argparse
import json
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import HydroConfig
from repro.hydro.stepper import rk3_step
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import parse_collectives_with_trips

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--multipod", action="store_true")
    ap.add_argument("--levels", type=int, default=4,
                    help="4 -> 4096 sub-grids of 8^3 (2M cells)")
    args = ap.parse_args()

    mesh = make_production_mesh(multi_pod=args.multipod)
    cfg = HydroConfig(subgrid=8, ghost=3, levels=args.levels)
    n = cfg.grids_per_edge * cfg.subgrid
    print(f"hydro dry-run: {cfg.n_subgrids} sub-grids of {cfg.subgrid}^3 "
          f"({n}^3 cells) on {mesh.size} chips")

    # spatial decomposition: x over data, y over model (and pod when
    # multi-pod) — the assembled-grid analogue of distributing sub-grids
    if args.multipod:
        spec = P(None, ("pod", "data"), "model", None)
    else:
        spec = P(None, "data", "model", None)
    u_sds = jax.ShapeDtypeStruct((5, n, n, n), jnp.float32)
    dt_sds = jax.ShapeDtypeStruct((), jnp.float32)

    step = partial(rk3_step, cfg=cfg, bc="periodic")
    with mesh:
        lowered = jax.jit(
            step,
            in_shardings=(NamedSharding(mesh, spec), None),
            out_shardings=NamedSharding(mesh, spec),
            donate_argnums=(0,),
        ).lower(u_sds, dt_sds)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    coll = parse_collectives_with_trips(compiled.as_text())
    result = {
        "scenario": "sedov", "mesh": "multipod" if args.multipod else "pod",
        "chips": mesh.size, "cells": cfg.cells_total,
        "subgrids": cfg.n_subgrids,
        "temp_bytes_per_device": int(mem.temp_size_in_bytes),
        "halo_collective_bytes_per_device": coll["total"],
        "collectives": {k: v for k, v in coll.items() if k != "total"},
    }
    print(json.dumps(result, indent=2))
    os.makedirs(RESULTS_DIR, exist_ok=True)
    name = f"hydro_dryrun_{result['mesh']}.json"
    with open(os.path.join(RESULTS_DIR, name), "w") as f:
        json.dump(result, f, indent=2)
    print("OK: hydro step compiles on the production mesh")


if __name__ == "__main__":
    main()
