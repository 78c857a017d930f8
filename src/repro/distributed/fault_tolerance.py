"""Fault tolerance, elastic scaling, and distributed-optimization tricks.

Design for 1000+ nodes (DESIGN.md §5):

* **Checkpoint/restart** — `repro.checkpoint` writes atomic, step-indexed,
  *logically-shaped* checkpoints; restart re-sharding onto a different mesh
  (grow/shrink by pods) is ``restore_resharded``.  The data pipeline is
  (seed, step)-addressable so the restored trajectory is bit-exact.
* **Failure detection & retry** — ``resilient_step`` wraps the train step:
  on a device/runtime error it reloads the last checkpoint and replays.
  Synchronous SPMD means a lost chip is a lost *job* without this outer
  loop; the checkpoint cadence bounds lost work to ``save_every`` steps.
* **Straggler mitigation** — synchronous pjit collectives make per-step
  progress the min over chips.  The knobs here: (a) bucketed static shapes
  (no recompile jitter — the aggregation ladder), (b) backup-worker
  speculation is NOT applicable inside one XLA program, so mitigation moves
  to the *data* layer: deterministic batches mean any replacement worker can
  recompute a shard without coordination.
* **Gradient compression** — ``make_dp_train_step`` is the explicit-DP
  variant (shard_map over the data axis) that int8-compresses the cross-pod
  gradient all-reduce with error feedback (repro.optim.compression): 4x
  fewer bytes on the slowest links, the dominant §Roofline collective term
  for multi-pod training.
* **Compute/communication overlap** — the pjit path leans on XLA latency
  hiding (scan-over-layers lets weight all-gathers for layer i+1 overlap
  layer i's compute); the explicit path interleaves per-leaf compressed
  reductions with the optimizer update loop.
"""
from __future__ import annotations

import logging
import time
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.optim.adamw import OptConfig, opt_update
from repro.optim.compression import compressed_allreduce

log = logging.getLogger("repro.ft")


# ---------------------------------------------------------------------------
# explicit-DP train step with compressed gradient reduction
# ---------------------------------------------------------------------------

def make_dp_train_step(loss_fn: Callable, opt_cfg: OptConfig, mesh: Mesh,
                       axis: str = "data", compress: bool = True):
    """shard_map DP train step: per-shard grads, (optionally int8) all-reduce,
    replicated update.  ``loss_fn(params, batch) -> scalar``."""
    def step(params, opt_state, residual, batch):
        def shard_body(params, opt_state, residual, batch):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(p, batch))(params)
            loss = jax.lax.pmean(loss, axis)
            flat_g, tdef = jax.tree_util.tree_flatten(grads)
            flat_r = jax.tree_util.tree_leaves(residual)
            reduced, new_res = [], []
            for g, r in zip(flat_g, flat_r):
                if compress:
                    m, nr = compressed_allreduce(
                        g.astype(jnp.float32), axis, r)
                else:
                    m, nr = jax.lax.pmean(g.astype(jnp.float32), axis), r
                reduced.append(m)
                new_res.append(nr)
            grads = jax.tree_util.tree_unflatten(tdef, reduced)
            residual = jax.tree_util.tree_unflatten(tdef, new_res)
            new_p, new_s, metrics = opt_update(grads, opt_state, params,
                                               opt_cfg)
            return new_p, new_s, residual, loss, metrics

        rep = P()
        dp = P(axis)
        batch_spec = jax.tree_util.tree_map(lambda _: dp, batch)
        param_spec = jax.tree_util.tree_map(lambda _: rep, params)
        opt_spec = jax.tree_util.tree_map(lambda _: rep, opt_state)
        res_spec = jax.tree_util.tree_map(lambda _: rep, residual)
        return jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(param_spec, opt_spec, res_spec, batch_spec),
            out_specs=(param_spec, opt_spec, res_spec, rep,
                       {"grad_norm": rep, "lr": rep}),
            check_vma=False,
        )(params, opt_state, residual, batch)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def residual_init(params):
    return jax.tree_util.tree_map(
        lambda p: jnp.zeros(p.shape, jnp.float32), params)


# ---------------------------------------------------------------------------
# resilient outer loop
# ---------------------------------------------------------------------------

class SimulatedFailure(RuntimeError):
    pass


def resilient_loop(step_fn: Callable, state: Tuple, n_steps: int, *,
                   save_every: int = 10,
                   save_fn: Optional[Callable] = None,
                   restore_fn: Optional[Callable] = None,
                   failure_hook: Optional[Callable[[int], None]] = None,
                   max_retries: int = 3) -> Tuple[Tuple, Dict[str, Any]]:
    """Run ``state = step_fn(state, step)`` with checkpoint/replay recovery.

    ``failure_hook(step)`` may raise ``SimulatedFailure`` (tests inject node
    loss); real deployments see ``jax.errors.JaxRuntimeError`` from a dead
    chip.  Recovery = restore last checkpoint + replay (deterministic data
    makes the replay exact).
    """
    stats = {"failures": 0, "restores": 0, "saved_steps": []}
    step = 0
    last_saved = None
    retries = 0
    while step < n_steps:
        try:
            if failure_hook is not None:
                failure_hook(step)
            state = step_fn(state, step)
            if save_fn is not None and (step + 1) % save_every == 0:
                save_fn(state, step + 1)
                last_saved = step + 1
                stats["saved_steps"].append(step + 1)
                retries = 0
            step += 1
        except (SimulatedFailure, jax.errors.JaxRuntimeError) as e:
            stats["failures"] += 1
            retries += 1
            if retries > max_retries:
                raise RuntimeError(
                    f"unrecoverable: {retries} consecutive failures") from e
            if restore_fn is not None and last_saved is not None:
                log.warning("step %d failed (%s); restoring step %d",
                            step, e, last_saved)
                state = restore_fn(last_saved)
                step = last_saved
                stats["restores"] += 1
            else:
                log.warning("step %d failed (%s); replaying step", step, e)
    return state, stats


# ---------------------------------------------------------------------------
# elastic re-scale
# ---------------------------------------------------------------------------

def rescale_state(params, opt_state, new_mesh: Mesh, spec_fn: Callable):
    """Re-place (params, opt_state) onto a new mesh (pod gained/lost).

    ``spec_fn(tree, mesh) -> tree of NamedSharding`` — the same rules used at
    startup, evaluated against the new mesh.
    """
    p_spec = spec_fn(params, new_mesh)
    o_spec = spec_fn(opt_state, new_mesh)
    params = jax.tree_util.tree_map(jax.device_put, params, p_spec)
    opt_state = jax.tree_util.tree_map(jax.device_put, opt_state, o_spec)
    return params, opt_state
