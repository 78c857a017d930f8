"""The control: the plain reference computed in bfloat16, put in the
program's place at the same steps, fails the committed limits, on six
seeds (at a size the CPU holds)."""
import jax.numpy as jnp
import pytest

import benchtest_util as util
import harness


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    root = util.copy_bench(str(tmp_path_factory.mktemp("bench")))
    util.add_tiny_cells(root)
    return harness.load_cell(root, f"{util.TINY}.s3")


@pytest.mark.parametrize("seed", [3, 17, 2 ** 31 + 5, 2 ** 33 + 9,
                                  2 ** 40 + 1, 2 ** 62 + 3])
def test_bfloat16_control_is_not_correct(cell, seed):
    limits = harness.load_json(
        f"{util.BENCH}/configs/sedov_t2.json")["limits"]
    reference = cell.module("reference")
    step = reference.make_step(cell.config)
    u = reference.initial_state(cell.config, seed)
    kept = []
    for k in range(cell.config["checked_steps"]):
        dt, u1 = step(u)
        kept.append((k, u, dt, u1))
        u = u1
    sound, _ = harness.judge(harness.compare_steps(reference, cell.config,
                                                   kept), 0, 0, limits)
    assert all(v["value"] <= v["limit"] for v in sound.values()), sound
    per_step = harness.compare_steps(reference, cell.config, kept,
                                     jnp.bfloat16)
    compared, failed = harness.judge(per_step, 0, 0, limits)
    assert failed == len(kept), compared
    assert compared["step_ulps"]["value"] > 3 * limits["step_ulps"]
