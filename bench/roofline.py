"""The chip's least time for a step: peaks by device kind, work by config.

Both come from files kept with the benchmark, never from a compiled
program: ``peaks.json`` (published figures keyed by exact
``device_kind``) and ``counts/<name>.json`` (frozen per-task counts),
turned into a step's FLOPs and bytes by the configuration's reference.
"""
from __future__ import annotations

import json
import os

BENCH = os.path.dirname(os.path.abspath(__file__))


def peaks(kind: str, bench: str = BENCH) -> dict:
    """Published peaks of ``kind``; a kind not in the table is an error."""
    with open(os.path.join(bench, "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"peaks.json (known: {sorted(table)})")
    return table[kind]


def least_step_s(config: dict, reference, peak: dict,
                 bench: str = BENCH) -> float:
    """Larger of FLOPs over peak FLOP/s and bytes over peak HBM bytes/s."""
    with open(os.path.join(bench, "counts", config["counts"] + ".json")) as f:
        counts = json.load(f)
    flops, hbm = reference.step_work(config, counts)
    return max(flops / peak["flops_per_s"], hbm / peak["hbm_bytes_per_s"])
