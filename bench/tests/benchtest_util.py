"""Helpers for the benchmark's tests: a copy of the benchmark with a
configuration small enough for the CPU, and the harness modules."""
from __future__ import annotations

import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(REPO, "src"))

TINY = "sedov_tiny"          # 8 sub-grids of 8^3: one bucket per stage


def copy_bench(dest: str) -> str:
    """``dest`` holding BENCHMARK.json, a copy of ``bench/`` (without its
    tests) and a link to the program's ``src``; returns ``dest``."""
    shutil.copytree(BENCH, os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    os.symlink(os.path.join(REPO, "src"), os.path.join(dest, "src"))
    return dest


def add_tiny_cells(root: str) -> None:
    """Add the configuration ``sedov_tiny`` (Table II's sizes at one
    octree level) with one cell per traffic mix, every metric in each."""
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "configs", "sedov_t2.json")) as f:
        config = json.load(f)
    config["hydro"]["levels"] = 1
    with open(os.path.join(bench, "configs", TINY + ".json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": TINY, "source": "test",
                            "file": f"bench/configs/{TINY}.json",
                            "reduced": ["levels"], "why": "CPU tests"})
    for traffic in ("s3", "fused"):
        spec["workloads"].append({"name": f"{TINY}.{traffic}",
                                  "config": TINY, "traffic": traffic,
                                  "chips": 1, "why": "CPU tests"})
    for m in spec["per_layer"]:
        m["workloads"] += [f"{TINY}.s3", f"{TINY}.fused"]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
