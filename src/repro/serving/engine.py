"""Continuous-batching serving engine built on the aggregation executor.

Each decode request is a fine-grained task: one new token against that
request's KV cache.  Launching per-request decode kernels starves the device
exactly like Octo-Tiger's per-sub-grid kernels; the engine therefore
aggregates active requests into bucketed batched ``decode_step`` launches —
strategy 3 at the serving layer:

* requests are admitted into free slots of a slot-array cache between steps
  (continuous batching = dynamic add/remove of sub-grids in the paper's AMR
  rebalancing analogy);
* each engine step launches ONE aggregated kernel over the smallest
  power-of-two bucket covering the active slots (bucketed static shapes);
* per-request ``cache_len`` makes the aggregated batch ragged-correct — each
  task owns its chunk of the shared buffers.

On TPU the slot-array cache stays resident and the gather/scatter below is
a cheap on-device permutation; the bucket ladder bounds compilation to
log2(max_batch) shapes.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AggregationConfig
from repro.core.compile_cache import enable_compile_cache
from repro.core.faults import FaultInjector, poison_slots
from repro.core.tunestore import TuneStore
from repro.data.pipeline import length_bucket
from repro.models import model as model_mod


class EngineOverloaded(RuntimeError):
    """``submit`` rejected a request because the engine cannot take it:
    the bounded pending queue is full (backpressure — the caller should
    retry later or route elsewhere), or the engine is draining/closed.
    Typed so load balancers can distinguish overload from bad input
    (``ValueError``)."""


@dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int = 16
    # wall-clock budget in seconds, counted from submit(): a request still
    # pending or still decoding past its deadline is SHED (failed + slot
    # recycled) instead of holding capacity nobody is waiting for
    deadline_s: Optional[float] = None
    # multi-tenant attribution (DESIGN.md §15): which "user" this request
    # belongs to — healthz() aggregates queue depth per tenant so a noisy
    # tenant is visible before it starves the others
    tenant: Any = 0
    output: List[int] = field(default_factory=list)
    done: bool = False
    failed: bool = False              # evicted by the guard (DESIGN.md §11)
    error: Optional[str] = None       # why, when failed
    _deadline: Optional[float] = field(default=None, repr=False)


class ServingEngine:
    def __init__(self, cfg, params, *, max_batch: int = 8,
                 max_len: int = 256,
                 max_pending: int = 0,
                 agg: Optional[AggregationConfig] = None,
                 fault_injector: Optional[FaultInjector] = None,
                 executor=None,
                 batcher=None):
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        # backpressure (DESIGN.md §14): 0 = unbounded (the PR 6 behaviour);
        # > 0 bounds ``pending`` and submit() rejects with EngineOverloaded
        self.max_pending = max(0, int(max_pending))
        # optional shared AggregationExecutor whose per-family circuit
        # breakers healthz() surfaces (duck-typed: anything with
        # ``breaker_states()``); the engine's own decode path is
        # self-contained and does not route through it
        self._executor = executor
        # optional TenantBatcher (DESIGN.md §15, duck-typed: anything with
        # ``healthz()``): when scenario tenants funnel through this
        # engine's deployment, healthz() republishes the batcher's
        # per-tenant queue depths and shard occupancy
        self._batcher = batcher
        self._draining = False
        self._closed = False
        self.agg = agg or AggregationConfig(max_aggregated=max_batch)
        self.guard = getattr(self.agg, "guard", "off")
        if self.guard not in ("off", "finite"):
            raise ValueError(
                f"guard={self.guard!r} — expected 'off' or 'finite'")
        self._injector = fault_injector
        # persistent warm start (DESIGN.md §13): the engine's per-bucket
        # decode programs are exactly the restart-latency hot spot — with
        # a tune store configured, point JAX's persistent compilation
        # cache at it so a restarted server's bucket compiles (and the
        # prefill programs) are disk hits instead of fresh XLA runs
        self._store = TuneStore.open(getattr(self.agg, "tune_store", None))
        warm = self._store is not None
        if warm:
            enable_compile_cache()
        self.buckets = tuple(b for b in self.agg.bucket_sizes()
                             if b <= max_batch) or (max_batch,)

        self.cache = model_mod.init_cache(cfg, params, self._stub_batch(),
                                          max_batch, max_len)
        self._fresh_cache = jax.tree_util.tree_map(lambda x: x, self.cache)
        # identify each cache leaf's slot (request) axis by probing the cache
        # structure at a different batch size — layer-count == batch-size
        # collisions make shape matching alone unreliable
        probe = jax.eval_shape(
            lambda: model_mod.init_cache(cfg, params,
                                         self._stub_batch(max_batch + 1),
                                         max_batch + 1, max_len))
        self._slot_axes = []
        for a, b in zip(jax.tree_util.tree_leaves(self.cache),
                        jax.tree_util.tree_leaves(probe)):
            axis = next((i for i, (x, y) in enumerate(zip(a.shape, b.shape))
                         if x != y), None)
            self._slot_axes.append(axis)
        self._treedef = jax.tree_util.tree_structure(self.cache)
        self.slots_free = list(range(max_batch))
        self.active: Dict[int, Request] = {}     # slot -> request
        self.pending: List[Request] = []
        self.next_token = np.zeros((max_batch,), np.int32)
        self._decode = {}                        # bucket -> jitted fn
        self._step_no = 0                        # launch counter ("wave" id)
        self.stats = {"launches": 0, "tokens": 0, "aggregated_hist": {},
                      "warm_start": warm,
                      "tune_store": (self._store.root
                                     if self._store is not None else None),
                      "faults": {"trips": 0, "evicted": 0, "shed": 0}}

    def _stub_batch(self, b: Optional[int] = None):
        cfg = self.cfg
        b = b or self.max_batch
        batch = {"tokens": jnp.zeros((b, 1), jnp.int32)}
        if cfg.family == "vlm":
            batch["vision"] = jnp.zeros((b, cfg.vision_tokens, cfg.d_model),
                                        jnp.float32)
        if cfg.family == "audio":
            batch["frames"] = jnp.zeros((b, 8, cfg.d_model), jnp.float32)
        return batch

    # -- admission ---------------------------------------------------------
    def submit(self, req: Request) -> None:
        """Queue one request, rejecting malformed input AT SUBMIT time —
        a bad request found during an aggregated decode step costs the
        whole co-batch a guard trip; found here it costs one ValueError."""
        prompt = req.prompt
        if not isinstance(prompt, (list, tuple)) or not prompt:
            raise ValueError(
                f"request {req.rid}: prompt must be a non-empty list of "
                f"token ids, got {type(prompt).__name__}")
        vocab = int(getattr(self.cfg, "vocab_size", 0))
        for t in prompt:
            if isinstance(t, bool) or not isinstance(t, (int, np.integer)):
                raise ValueError(
                    f"request {req.rid}: prompt token {t!r} is not an int")
            if t < 0 or (vocab and t >= vocab):
                raise ValueError(
                    f"request {req.rid}: prompt token {int(t)} outside "
                    f"[0, {vocab})")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be >= 1, got "
                f"{req.max_new_tokens}")
        if len(prompt) + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({len(prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds the "
                f"engine's max_len {self.max_len}")
        if req.deadline_s is not None and req.deadline_s <= 0:
            raise ValueError(
                f"request {req.rid}: deadline_s must be > 0, got "
                f"{req.deadline_s}")
        if self._closed or self._draining:
            raise EngineOverloaded(
                f"request {req.rid}: engine is "
                f"{'closed' if self._closed else 'draining'} — not "
                f"accepting new requests")
        if self.max_pending and len(self.pending) >= self.max_pending:
            raise EngineOverloaded(
                f"request {req.rid}: pending queue full "
                f"({len(self.pending)}/{self.max_pending}) — retry later")
        if req.deadline_s is not None:
            req._deadline = time.monotonic() + req.deadline_s
        self.pending.append(req)

    def _shed(self, req: Request, where: str) -> None:
        req.failed = True
        req.done = True
        req.error = (f"request {req.rid}: deadline_s={req.deadline_s} "
                     f"exceeded {where} — shed")
        self.stats["faults"]["shed"] += 1

    def _shed_expired(self) -> None:
        """Deadline shedding (DESIGN.md §14): a past-deadline request is
        dead weight — nobody is waiting for its tokens — so it is failed
        and its capacity (queue entry or live slot) recycled before the
        next admit/launch.  The freed slot's cache garbage is harmless:
        admission re-zeroes slot state before reuse."""
        now = time.monotonic()
        kept = []
        for req in self.pending:
            if req._deadline is not None and now > req._deadline:
                self._shed(req, "while queued")
            else:
                kept.append(req)
        self.pending = kept
        for slot, req in list(self.active.items()):
            if req._deadline is not None and now > req._deadline:
                self._shed(req, f"mid-decode (slot {slot})")
                del self.active[slot]
                self.slots_free.append(slot)

    def _admit(self) -> None:
        while self.pending and self.slots_free:
            slot = self.slots_free.pop()
            req = self.pending.pop(0)
            self.active[slot] = req
            # reset this slot's cache_len and prefill the prompt
            self.cache["len"] = self.cache["len"].at[slot].set(0)
            self._zero_slot_states(slot)
            for tok in req.prompt[:-1]:
                self._prefill_token(slot, tok)
                if req.failed:        # guard evicted it mid-prefill
                    break
            if req.failed:
                continue              # slot already recycled by the guard
            self.next_token[slot] = req.prompt[-1]

    def _zero_slot_states(self, slot: int) -> None:
        """Reset one slot to its FRESH-cache values (not zeros: recurrent
        states like the mLSTM stabilizer initialize to -inf-like values, and
        zeroing them would corrupt the first decode of a reused slot)."""
        leaves = jax.tree_util.tree_leaves(self.cache)
        fresh = jax.tree_util.tree_leaves(self._fresh_cache)
        out = []
        for x, f, axis in zip(leaves, fresh, self._slot_axes):
            if axis is None:
                out.append(x)
            else:
                idx = (slice(None),) * axis + (slot,)
                out.append(x.at[idx].set(f[idx]))
        clen = self.cache["len"]
        self.cache = jax.tree_util.tree_unflatten(self._treedef, out)
        self.cache["len"] = clen

    def _prefill_token(self, slot: int, tok: int) -> None:
        """Single-slot prefill through the bucket-1 decode path (simple and
        correct; a production engine would run chunked prefill)."""
        self._launch(np.array([slot]), np.array([tok], np.int32))

    # -- the aggregated decode launch ---------------------------------------
    def _decode_fn(self, bucket: int):
        fn = self._decode.get(bucket)
        if fn is None:
            cfg, params = self.cfg, self.params

            def fwd(cache, slot_idx, toks):
                leaves = jax.tree_util.tree_leaves(cache)
                sub_leaves = [
                    x if ax is None else jnp.take(x, slot_idx, axis=ax)
                    for x, ax in zip(leaves, self._slot_axes)]
                sub = jax.tree_util.tree_unflatten(self._treedef, sub_leaves)
                logits, sub = model_mod.decode_step(cfg, params, sub,
                                                    toks[:, None])
                new_leaves = []
                for full, part, ax in zip(leaves,
                                          jax.tree_util.tree_leaves(sub),
                                          self._slot_axes):
                    if ax is None:
                        new_leaves.append(full)
                    else:
                        sl = (slice(None),) * ax + (slot_idx,)
                        new_leaves.append(full.at[sl].set(part))
                new_cache = jax.tree_util.tree_unflatten(self._treedef,
                                                         new_leaves)
                return logits, new_cache

            fn = jax.jit(fwd)
            self._decode[bucket] = fn
        return fn

    def _launch(self, slots: np.ndarray, toks: np.ndarray) -> np.ndarray:
        n = len(slots)
        bucket = length_bucket(n, self.buckets)
        pad = bucket - n
        if pad:
            # pad lanes target a FREE slot (one must exist when n < bucket
            # <= max_batch): they scatter garbage into a slot whose cache is
            # reset on admission, never into a live request's chunk.
            spare = next(s for s in range(self.max_batch)
                         if s not in set(slots.tolist()))
            slots_in = np.concatenate([slots, np.full(pad, spare, np.int64)])
            toks_in = np.concatenate([toks, np.zeros(pad, np.int32)])
        else:
            slots_in, toks_in = slots, toks
        logits, new_cache = self._decode_fn(bucket)(
            self.cache, jnp.asarray(slots_in), jnp.asarray(toks_in))
        logits = logits[:n]
        self._step_no += 1
        if self._injector is not None:
            # payload site at the serving layer: one tenant's logits row
            # goes non-finite (a poisoned request), keyed by request id
            rids = [self.active[s].rid for s in slots.tolist()]
            hit = self._injector.poison_positions("decode", self._step_no,
                                                  rids)
            if hit:
                logits = poison_slots(logits, sorted(hit), hit)
        if self.guard == "finite":
            logits = self._guard_rows(slots, logits)
        self.stats["launches"] += 1
        h = self.stats["aggregated_hist"]
        h[bucket] = h.get(bucket, 0) + 1
        self.cache = new_cache
        return np.asarray(jnp.argmax(logits, axis=-1))

    def _guard_rows(self, slots: np.ndarray, logits) -> jnp.ndarray:
        """ONE scalar finite-check per aggregated launch; only a trip pays
        for the per-row verdict.  A non-finite row belongs to exactly one
        request (slot-array decode is batch-exact): that request is marked
        failed and EVICTED, its slot recycled, while the co-batched
        tenants' rows — untouched by the offender — decode on normally.
        The evicted slot's cache garbage is harmless: admission re-zeroes
        a slot's state before reuse."""
        n = int(logits.shape[0])
        if bool(jnp.all(jnp.isfinite(logits))):
            return logits
        self.stats["faults"]["trips"] += 1
        row_ok = np.asarray(jnp.all(jnp.isfinite(logits.reshape(n, -1)),
                                    axis=1))
        for i, slot in enumerate(slots.tolist()):
            if row_ok[i]:
                continue
            req = self.active[slot]
            req.failed = True
            req.done = True
            req.error = (f"request {req.rid}: non-finite logits at decode "
                         f"step {self._step_no} (slot {slot}) — evicted")
            del self.active[slot]
            self.slots_free.append(slot)
            self.stats["faults"]["evicted"] += 1
        # keep argmax well-defined on the dead rows (their token is never
        # delivered — the owning request is already gone)
        return jnp.nan_to_num(logits, nan=0.0, posinf=0.0, neginf=0.0)

    # -- engine loop ---------------------------------------------------------
    def step(self) -> int:
        """One engine iteration: shed, admit, aggregate, launch, collect."""
        self._shed_expired()
        self._admit()
        if not self.active:
            return 0
        slots = np.array(sorted(self.active.keys()))
        toks = self.next_token[slots]
        out = self._launch(slots, toks)
        finished = []
        for i, slot in enumerate(slots):
            req = self.active.get(slot)
            if req is None:           # evicted by the guard mid-launch
                continue
            tok = int(out[i])
            req.output.append(tok)
            self.next_token[slot] = tok
            if len(req.output) >= req.max_new_tokens:
                req.done = True
                finished.append(slot)
        for slot in finished:
            del self.active[slot]
            self.slots_free.append(slot)
        self.stats["tokens"] += len(slots)
        return len(slots)

    def run(self, max_steps: int = 1000) -> None:
        for _ in range(max_steps):
            if not self.pending and not self.active:
                break
            self.step()

    # -- health + lifecycle (DESIGN.md §14) ----------------------------------
    def healthz(self) -> Dict[str, object]:
        """One-call health surface for load balancers: capacity (free
        slots / queue depth vs bounds), lifecycle state, the cumulative
        fault counters, and — when a shared aggregation executor is
        attached — its per-family circuit-breaker states."""
        f = self.stats["faults"]
        return {
            "slots_free": len(self.slots_free),
            "active": len(self.active),
            "queue_depth": len(self.pending),
            "max_pending": self.max_pending,
            "max_batch": self.max_batch,
            "draining": self._draining,
            "closed": self._closed,
            "trips": f["trips"],
            "evicted": f["evicted"],
            "shed": f["shed"],
            "breakers": (self._executor.breaker_states()
                         if self._executor is not None else {}),
            "tenants": self._tenant_health(),
        }

    def _tenant_health(self) -> Dict[str, object]:
        """Per-tenant queue depth + shard occupancy (DESIGN.md §15):
        the engine's own pending/active requests grouped by their
        ``tenant`` tag, merged with the attached TenantBatcher's scenario
        tenants when one is wired in."""
        queue: Dict[object, int] = {}
        for r in self.pending:
            queue[r.tenant] = queue.get(r.tenant, 0) + 1
        active: Dict[object, int] = {}
        for r in self.active.values():
            active[r.tenant] = active.get(r.tenant, 0) + 1
        out = {"count": 0, "queue_depth": queue, "active": active,
               "shard_occupancy": []}
        if self._batcher is not None:
            bh = self._batcher.healthz()
            for tid, depth in bh.get("queue_depth", {}).items():
                out["queue_depth"][tid] = (out["queue_depth"].get(tid, 0)
                                           + depth)
            out["shard_occupancy"] = bh.get("shard_occupancy", [])
        out["count"] = len(set(out["queue_depth"]) | set(active))
        return out

    def drain(self, max_steps: int = 10000) -> None:
        """Graceful drain: stop admitting NEW requests (submit raises
        EngineOverloaded) but run the engine until everything already
        accepted has finished, been evicted, or been shed."""
        self._draining = True
        self.run(max_steps)

    def close(self, max_steps: int = 10000) -> None:
        """Drain, then close permanently and drop the decode-program and
        slot-cache references (a closed engine rejects every submit)."""
        self.drain(max_steps)
        self._closed = True
        self._decode.clear()
