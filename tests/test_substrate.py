"""Optimizer, data pipeline, checkpoint/restart, fault tolerance, sharding API."""
import os
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.checkpoint import (
    latest_step, restore_checkpoint, save_checkpoint,
)
from repro.data.pipeline import DataConfig, SyntheticLMStream, length_bucket
from repro.distributed.api import logical_rules, spec_for
from repro.distributed.fault_tolerance import (
    SimulatedFailure, resilient_loop,
)
from repro.optim.adamw import (
    OptConfig, clip_by_global_norm, cosine_lr, global_norm, opt_init,
    opt_update,
)
from repro.optim.compression import int8_compress, int8_decompress


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------

def test_adamw_converges_quadratic():
    params = {"w": jnp.array([5.0, -3.0])}
    state = opt_init(params)
    cfg = OptConfig(lr=0.2, warmup_steps=0, total_steps=200,
                    weight_decay=0.0, clip_norm=100.0)
    for _ in range(150):
        grads = {"w": 2 * params["w"]}
        params, state, _ = opt_update(grads, state, params, cfg)
    assert float(jnp.max(jnp.abs(params["w"]))) < 0.1


def test_cosine_schedule_shape():
    cfg = OptConfig(lr=1.0, warmup_steps=10, total_steps=100)
    assert float(cosine_lr(cfg, 0)) == pytest.approx(0.1)
    assert float(cosine_lr(cfg, 9)) == pytest.approx(1.0)
    assert float(cosine_lr(cfg, 55)) == pytest.approx(0.5, abs=0.05)
    assert float(cosine_lr(cfg, 99)) < 0.01


def test_grad_clip():
    tree = {"a": jnp.array([3.0, 4.0])}
    assert float(global_norm(tree)) == pytest.approx(5.0)
    clipped, norm = clip_by_global_norm(tree, 1.0)
    assert float(global_norm(clipped)) == pytest.approx(1.0)
    assert float(norm) == pytest.approx(5.0)


def test_adamw_bf16_params_fp32_state():
    params = {"w": jnp.ones((4,), jnp.bfloat16)}
    state = opt_init(params)
    assert state["m"]["w"].dtype == jnp.float32
    grads = {"w": jnp.full((4,), 0.1, jnp.float32)}
    new_p, new_s, _ = opt_update(grads, state, params, OptConfig())
    assert new_p["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------

def test_int8_roundtrip_error_bounded():
    g = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    q, scale = int8_compress(g)
    back = int8_decompress(q, scale)
    assert float(jnp.max(jnp.abs(back - g))) <= float(scale) * 0.5 + 1e-6


def test_compressed_allreduce_error_feedback():
    """Across steps, error feedback keeps the accumulated bias near zero."""
    mesh = jax.make_mesh((1,), ("data",))
    from repro.optim.compression import compressed_allreduce

    def step(g, res):
        return jax.shard_map(
            lambda g, r: compressed_allreduce(g, "data", r),
            mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)(g, res)

    g = jax.random.normal(jax.random.PRNGKey(1), (64,))
    res = jnp.zeros_like(g)
    total_true, total_sent = jnp.zeros_like(g), jnp.zeros_like(g)
    for i in range(20):
        mean, res = step(g, res)
        total_true += g
        total_sent += mean
    # error feedback: cumulative quantization error stays O(one step's scale)
    assert float(jnp.max(jnp.abs(total_sent - total_true))) < \
        float(jnp.max(jnp.abs(g))) * 0.02


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------

def test_data_deterministic_by_step():
    cfg = DataConfig(seq_len=32, global_batch=4, vocab_size=64, seed=7)
    a = SyntheticLMStream(cfg).batch(13)
    b = SyntheticLMStream(cfg).batch(13)
    np.testing.assert_array_equal(np.asarray(a["tokens"]),
                                  np.asarray(b["tokens"]))
    c = SyntheticLMStream(cfg).batch(14)
    assert not np.array_equal(np.asarray(a["tokens"]), np.asarray(c["tokens"]))


def test_data_labels_are_shifted_tokens():
    cfg = DataConfig(seq_len=16, global_batch=2, vocab_size=32)
    b = SyntheticLMStream(cfg).batch(0)
    np.testing.assert_array_equal(np.asarray(b["labels"][:, :-1]),
                                  np.asarray(b["tokens"][:, 1:]))


def test_length_bucket():
    assert length_bucket(1, (1, 2, 4, 8)) == 1
    assert length_bucket(3, (1, 2, 4, 8)) == 4
    assert length_bucket(9, (1, 2, 4, 8)) == 8   # clamps at max


# ---------------------------------------------------------------------------
# checkpoint / restart
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip(tmp_path):
    params = {"layer": {"w": jnp.arange(6.0).reshape(2, 3),
                        "b": jnp.ones((3,), jnp.bfloat16)}}
    opt = opt_init(params)
    save_checkpoint(str(tmp_path), 42, params, opt, meta={"arch": "x"})
    assert latest_step(str(tmp_path)) == 42
    p2, o2, meta = restore_checkpoint(str(tmp_path), 42, params, opt)
    np.testing.assert_array_equal(np.asarray(p2["layer"]["w"]),
                                  np.asarray(params["layer"]["w"]))
    assert p2["layer"]["b"].dtype == jnp.bfloat16
    assert int(o2["step"]) == 0 and meta["arch"] == "x"


def test_checkpoint_latest_of_many(tmp_path):
    params = {"w": jnp.zeros((2,))}
    opt = opt_init(params)
    for s in (10, 20, 30):
        save_checkpoint(str(tmp_path), s, params, opt)
    assert latest_step(str(tmp_path)) == 30


def test_resilient_loop_replays_from_checkpoint(tmp_path):
    """Training survives injected node failures; trajectory is exact."""
    saves = {}

    def step_fn(state, step):
        return state + 1

    def save_fn(state, step):
        saves[step] = state

    def restore_fn(step):
        return saves[step]

    fail_at = {7, 13}

    def failure_hook(step):
        if step in fail_at:
            fail_at.remove(step)
            raise SimulatedFailure(f"node lost at step {step}")

    state, stats = resilient_loop(
        step_fn, 0, 20, save_every=5, save_fn=save_fn,
        restore_fn=restore_fn, failure_hook=failure_hook)
    assert state == 20                  # exact trajectory despite 2 failures
    assert stats["failures"] == 2
    assert stats["restores"] == 2


def test_resilient_loop_gives_up_after_retries():
    def failure_hook(step):
        raise SimulatedFailure("dead node")
    with pytest.raises(RuntimeError, match="unrecoverable"):
        resilient_loop(lambda s, i: s, 0, 5, save_every=1,
                       failure_hook=failure_hook, max_retries=2)


# ---------------------------------------------------------------------------
# sharding rules (no devices needed: fake mesh with .shape dict)
# ---------------------------------------------------------------------------

def _fake_mesh(**axes):
    return SimpleNamespace(shape=dict(axes))


def test_spec_divisibility_fallback():
    with logical_rules(_fake_mesh(pod=2, data=16, model=16)):
        # batch 256 shards over pod+data
        assert spec_for((256, 128), ["batch", None]) == P(("pod", "data"), None)
        # batch 1 (long-context decode) cannot shard -> replicated
        assert spec_for((1, 128), ["batch", None]) == P(None, None)
        # 8 kv heads on 16-way model axis -> replicated
        assert spec_for((4096, 8), [None, "kv_heads"]) == P(None, None)
        # 32 heads shard fine
        assert spec_for((4096, 32), [None, "heads"]) == P(None, "model")


def test_spec_used_axes_fall_through():
    with logical_rules(_fake_mesh(pod=2, data=16, model=16),
                       {"kv_seq": ("pod", "data", "model")}):
        # batch takes pod+data; kv_seq falls through to model
        s = spec_for((128, 32768, 8, 128),
                     ["batch", "kv_seq", "kv_heads", None])
        assert s == P(("pod", "data"), "model", None, None)
        # batch-1: kv_seq absorbs everything
        s = spec_for((1, 524288, 8, 128),
                     ["batch", "kv_seq", "kv_heads", None])
        assert s == P(None, ("pod", "data", "model"), None, None)


def test_constrain_noop_without_context():
    from repro.distributed.api import constrain
    x = jnp.ones((4, 4))
    assert constrain(x, "batch", "embed") is x
