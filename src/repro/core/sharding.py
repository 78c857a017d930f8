"""Sharded multi-device aggregation runtime + multi-tenant batching
(DESIGN.md §15).

The single-device :class:`~repro.core.aggregation.AggregationExecutor`
solved the paper's node-level problem: fine-grained tasks -> bucketed
batched launches.  This module adds the HPX *distribution* half on the
same executor abstraction: a :class:`ShardedAggregationExecutor`
partitions every submitted range across a ``jax.sharding.Mesh`` using the
``distributed/api.py`` logical-axis rules (``"subgrid": ("pod", "data")``)
and drains **device-local bucket ladders inside one ``shard_map``
program** — every device walks the identical greedy bucket sequence over
its own shard, so no device ever idles waiting for another device's wave,
and the per-task arithmetic is the exact batched body the single-device
executor launches (bit-identity is preserved by construction: no padding,
no reordering, same compiled per-chunk programs).

Wave anatomy for a range of ``count`` tasks over ``S`` shards::

    local  = count // S                # per-device wave
    n_even = local * S                 # ONE shard_map launch, S-way SPMD
    rem    = count - n_even            # one unsharded chunked launch
                                       # (same ladder, same body)

Both launches dispatch asynchronously through the :class:`ExecutorPool`
before ANY result is consumed (flush/exchange overlap); the guard audit
and future fulfilment only force values afterwards.  Collectives appear
ONLY at the assemble/finalize boundary: ``ghost_gather`` replicates a
sharded wave output (the all-gather), ``halo_exchange`` rolls shard
edges around the ``data`` ring via ``ppermute``.  Inside a wave there is
no cross-device traffic at all.

:class:`TenantBatcher` is the multi-tenant layer on top: MANY independent
small scenario instances ("users" running their own Sedov/gravity cases)
funnel their populations into ONE executor's waves.  The bucket ladder
does not care which tenant a slot came from, so rising tenancy *fills*
buckets — per-instance cost drops as tenant count rises (the
``BENCH_sharded_tenancy.json`` acceptance sweep).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map

from repro.configs.base import AggregationConfig
from repro.core.aggregation import (RangeFuture, TaskFuture, TaskSignature,
                                    _backend_key, _spec_of, gather_futures,
                                    greedy_decomposition)
from repro.core.executor import ExecutorPool
from repro.core.faults import (FaultInjector, TaskFailedError, poison_slots)
from repro.core.trace import named, program_name, span
from repro.distributed.api import DEFAULT_RULES, subgrid_mesh

# the logical axes a task range distributes over (DEFAULT_RULES["subgrid"])
SUBGRID_AXES: Tuple[str, ...] = tuple(DEFAULT_RULES["subgrid"])


def _task_signature(kernel: str, parents: Sequence[Any]) -> TaskSignature:
    """Region key from parent arrays/specs: per-task = parent minus the
    leading task axis (mirrors ``TaskSignature.from_args`` on SlotViews)."""
    return TaskSignature(kernel, tuple(
        (tuple(p.shape[1:]), np.dtype(p.dtype).str) for p in parents))


class _ShardRegion:
    """One kernel family's sharded lane: ladder, compiled drain programs,
    queued range entries, and the per-family stats surface (same keys the
    single-device ``_Region`` publishes, so bench_util extractors work
    unchanged)."""

    __slots__ = ("signature", "kernel", "batched_fn", "ladder", "queue",
                 "singles", "compiled", "waves", "stats")

    def __init__(self, signature: TaskSignature, batched_fn: Callable,
                 ladder: Tuple[int, ...]):
        self.signature = signature
        self.kernel = signature.kernel
        self.batched_fn = batched_fn
        self.ladder = ladder
        self.queue: List["_ShardPending"] = []
        self.singles: List[Tuple[TaskFuture, Tuple[Any, ...]]] = []
        self.compiled: Dict[Tuple, Callable] = {}
        self.waves = 0
        self.stats: Dict[str, Any] = {
            "submitted": 0, "launches": 0, "sharded_launches": 0,
            "remainder_launches": 0, "aggregated_hist": {},
            "ladder": list(ladder), "shard_occupancy": [],
            "breaker": "closed", "measurement_launches": 0,
            "faults": {"injected": 0, "trips": 0, "isolated": 0},
        }


class _ShardPending:
    """One queued contiguous range (the only queue-entry kind: per-task
    ``submit``s are stacked into a synthetic range at flush time)."""

    __slots__ = ("future", "parents", "start", "count", "wave_base",
                 "singles")

    def __init__(self, future: RangeFuture, parents: Tuple[Any, ...],
                 start: int, count: int,
                 singles: Optional[List[TaskFuture]] = None):
        self.future = future
        self.parents = parents
        self.start = start
        self.count = count
        self.wave_base = 0          # wave-relative id of task 0 (set at flush)
        self.singles = singles      # fan-out targets for stacked submits


class _ShardLaunch:
    """Audit record for one in-flight launch: which slice of which entry's
    future it fulfils once the guard has seen it."""

    __slots__ = ("region", "entry", "out", "off", "n", "wave")

    def __init__(self, region, entry, out, off, n, wave):
        self.region = region
        self.entry = entry
        self.out = out
        self.off = off              # offset within the entry's range
        self.n = n
        self.wave = wave


class ShardedAggregationExecutor:
    """Drop-in multi-device twin of ``AggregationExecutor`` (the strategy
    runner swaps it in via ``Strategy.executor_cls``): same constructor
    shape, same ``register``/``submit``/``submit_range``/``flush``/
    ``warmup``/``stats`` surface — but every range drains as one
    ``shard_map`` program over the subgrid mesh plus one unsharded
    remainder launch.

    What is intentionally NOT here (single-device-only machinery): the
    measured cost model / flush policies (the sharded wave is always
    drained eagerly — policy tuning is a per-device concern the local
    ladder already encodes), launch watchdog and circuit breakers
    (``breaker_states`` reports every family closed), and host staging
    (a per-task host loop defeats the point of sharding; construction
    rejects ``staging="host"``).
    """

    def __init__(self, batched_fn: Optional[Callable] = None,
                 config: Optional[AggregationConfig] = None,
                 pool: Optional[ExecutorPool] = None,
                 buffer_pool: Optional[Any] = None,
                 donate: bool = False,
                 name: str = "region",
                 fault_injector: Optional[FaultInjector] = None,
                 mesh: Optional[Mesh] = None):
        self.name = name
        self.config = config or AggregationConfig()
        if getattr(self.config, "staging", "device") == "host":
            raise ValueError(
                "ShardedAggregationExecutor requires staging='device' — "
                "host staging re-serializes the per-task loop the sharded "
                "drain exists to remove")
        self.pool = pool or ExecutorPool(self.config.n_executors)
        self.mesh = mesh if mesh is not None else subgrid_mesh(
            getattr(self.config, "shard_devices", 0) or 0)
        self.n_shards = int(np.prod([self.mesh.shape[a]
                                     for a in SUBGRID_AXES]))
        self._sharding = NamedSharding(self.mesh, P(SUBGRID_AXES))
        self._replicated = NamedSharding(self.mesh, P())
        self._replicate_jit = jax.jit(lambda a: a,
                                      out_shardings=self._replicated)
        self._buckets = tuple(sorted(self.config.bucket_sizes()))
        self._guard = getattr(self.config, "guard", "off")
        if self._guard not in ("off", "finite"):
            raise ValueError(f"unknown guard mode {self._guard!r}")
        self._injector = fault_injector
        self._bodies: Dict[str, Callable] = {}
        self._regions: Dict[TaskSignature, _ShardRegion] = {}
        self._default_kernel: Optional[str] = None
        self.stats: Dict[str, Any] = {
            "submitted": 0, "launches": 0, "aggregated_hist": {},
            "staging_s": 0.0, "regions": {}, "warm_start": False,
            "flush_policy": "eager", "backend_key": _backend_key(),
            "mesh": {a: int(s) for a, s in self.mesh.shape.items()},
            "n_shards": self.n_shards,
            "shard_occupancy": [0] * self.n_shards,
        }
        if batched_fn is not None:
            self.register(name, batched_fn)

    # -- region registry ---------------------------------------------------
    def register(self, kernel: str, batched_fn: Callable,
                 default: bool = False) -> str:
        self._bodies[kernel] = batched_fn
        if default or self._default_kernel is None:
            self._default_kernel = kernel
        return kernel

    def _resolve_kernel(self, kernel: Optional[str]) -> str:
        k = kernel or self._default_kernel
        if k is None or k not in self._bodies:
            raise KeyError(f"unknown kernel family {k!r} — register() it "
                           f"before submitting")
        return k

    def _region_for(self, kernel: str,
                    parents: Sequence[Any]) -> _ShardRegion:
        sig = _task_signature(kernel, parents)
        region = self._regions.get(sig)
        if region is None:
            region = _ShardRegion(sig, self._bodies[kernel], self._buckets)
            self._regions[sig] = region
            self.stats["regions"][sig.describe()] = region.stats
        return region

    # -- submission --------------------------------------------------------
    def submit_range(self, parents: Tuple[Any, ...], start: int, n: int,
                     kernel: Optional[str] = None) -> RangeFuture:
        """One contiguous range of ``n`` tasks referencing device-resident
        ``parents`` — the bulk path ``TaskPopulation.submit_to`` uses."""
        kernel = self._resolve_kernel(kernel)
        if n < 1:
            raise ValueError(f"range of {n} tasks — need at least 1")
        limit = min(p.shape[0] for p in parents)
        if not 0 <= start <= limit - n:
            raise IndexError(f"range [{start}, {start + n}) outside parent "
                             f"task axis of length {limit}")
        region = self._region_for(kernel, parents)
        fut = RangeFuture(n)
        region.queue.append(_ShardPending(fut, tuple(parents), start, n))
        region.stats["submitted"] += n
        self.stats["submitted"] += n
        return fut

    def submit(self, *args, kernel: Optional[str] = None) -> TaskFuture:
        """One fine-grained task (per-task args).  Stacked into a synthetic
        range at flush, so singles ride the same sharded drain."""
        kernel = self._resolve_kernel(kernel)
        sig = TaskSignature.from_args(kernel, args)
        region = self._regions.get(sig)
        if region is None:
            region = _ShardRegion(sig, self._bodies[kernel], self._buckets)
            self._regions[sig] = region
            self.stats["regions"][sig.describe()] = region.stats
        fut = TaskFuture()
        region.singles.append((fut, tuple(jnp.asarray(a) for a in args)))
        region.stats["submitted"] += 1
        self.stats["submitted"] += 1
        return fut

    # -- compiled drain programs -------------------------------------------
    def _arg_key(self, parents: Sequence[Any]) -> Tuple:
        return tuple(_spec_of(p) for p in parents)

    def _sharded_fn(self, region: _ShardRegion, local: int,
                    parents: Sequence[Any]) -> Callable:
        """ONE jitted shard_map program: every shard drains its ``local``
        tasks through the identical greedy bucket sequence and concatenates
        the chunk outputs — trace-time loop, zero padding."""
        key = ("shard", local, self._arg_key(parents))
        fn = region.compiled.get(key)
        if fn is None:
            chunks = greedy_decomposition(local, region.ladder)
            body = region.batched_fn
            spec = P(SUBGRID_AXES)

            def local_drain(*args):
                outs, s = [], 0
                for b in chunks:
                    part = tuple(jax.lax.slice_in_dim(a, s, s + b, axis=0)
                                 for a in args)
                    outs.append(body(*part))
                    s += b
                if len(outs) == 1:
                    return outs[0]
                return jax.tree_util.tree_map(
                    lambda *xs: jnp.concatenate(xs), *outs)

            # each shard's tasks are independent and nothing is exchanged,
            # so there is no varying-axes type to check; a Pallas body's
            # output carries none
            fn = jax.jit(named(shard_map(local_drain, mesh=self.mesh,
                                         in_specs=spec, out_specs=spec,
                                         check_vma=False),
                               program_name(region.kernel, local)))
            region.compiled[key] = fn
        return fn

    def _chunked_fn(self, region: _ShardRegion, count: int,
                    parents: Sequence[Any]) -> Callable:
        """Unsharded greedy-ladder drain for the remainder (< one task per
        shard of headroom): same body, same bucket sequence a single-device
        executor would launch, composed into one program."""
        key = ("rem", count, self._arg_key(parents))
        fn = region.compiled.get(key)
        if fn is None:
            chunks = greedy_decomposition(count, region.ladder)
            body = region.batched_fn

            def drain(*args):
                outs, s = [], 0
                for b in chunks:
                    part = tuple(jax.lax.slice_in_dim(a, s, s + b, axis=0)
                                 for a in args)
                    outs.append(body(*part))
                    s += b
                if len(outs) == 1:
                    return outs[0]
                return jax.tree_util.tree_map(
                    lambda *xs: jnp.concatenate(xs), *outs)

            fn = jax.jit(named(drain, program_name(region.kernel, count)))
            region.compiled[key] = fn
        return fn

    # -- flush: dispatch everything, THEN settle ---------------------------
    def flush(self) -> None:
        """Drain every region's queue.  Two phases: (1) dispatch — every
        sharded wave and every remainder launch enters the executor pool
        asynchronously, nothing is forced; (2) settle — injection, guard
        audit and future fulfilment consume results.  Phase 1 never blocks
        on phase 2's work, which is the flush/exchange overlap: by the
        time the first wave's values are read, every device already holds
        its whole flush worth of work."""
        recs: List[_ShardLaunch] = []
        for region in self._regions.values():
            if region.singles:
                self._pack_singles(region)
            if not region.queue:
                continue
            cursor = 0
            occupancy = [0] * self.n_shards
            for entry in region.queue:
                entry.wave_base = cursor
                cursor += entry.count
                recs.extend(self._dispatch(region, entry, occupancy))
            region.queue = []
            region.stats["shard_occupancy"] = occupancy
            self.stats["shard_occupancy"] = occupancy
            region.waves += 1
        for rec in recs:
            self._settle(rec)
        self.pool.drain()

    def _pack_singles(self, region: _ShardRegion) -> None:
        singles = region.singles
        region.singles = []
        futs = [f for f, _ in singles]
        parents = tuple(jnp.stack(col)
                        for col in zip(*(a for _, a in singles)))
        region.queue.append(_ShardPending(RangeFuture(len(futs)), parents,
                                          0, len(futs), singles=futs))

    def _stage(self, args: Sequence[Any], kernel: str,
               bucket: int) -> Tuple[Any, ...]:
        with span("repro.staging", self.stats, "staging_s", kernel=kernel,
                  bucket=bucket):
            return tuple(
                a if getattr(a, "sharding", None) == self._sharding
                else jax.device_put(a, self._sharding)
                for a in args)

    def _dispatch(self, region: _ShardRegion, entry: _ShardPending,
                  occupancy: List[int]) -> List[_ShardLaunch]:
        recs = []
        local = entry.count // self.n_shards
        n_even = local * self.n_shards
        rem = entry.count - n_even
        if local:
            args = tuple(
                p if entry.start == 0 and p.shape[0] == n_even
                else jax.lax.slice_in_dim(p, entry.start,
                                          entry.start + n_even, axis=0)
                for p in entry.parents)
            out = self.pool.get().launch(
                self._sharded_fn(region, local, args),
                *self._stage(args, region.kernel, local),
                family=region.kernel, bucket=local)
            recs.append(_ShardLaunch(region, entry, out, 0, n_even,
                                     region.waves))
            hist = region.stats["aggregated_hist"]
            for b in greedy_decomposition(local, region.ladder):
                hist[b] = hist.get(b, 0) + 1
                self.stats["aggregated_hist"][b] = \
                    self.stats["aggregated_hist"].get(b, 0) + 1
            region.stats["launches"] += 1
            region.stats["sharded_launches"] += 1
            self.stats["launches"] += 1
            for i in range(self.n_shards):
                occupancy[i] += local
        if rem:
            s0 = entry.start + n_even
            args = tuple(jax.lax.slice_in_dim(p, s0, s0 + rem, axis=0)
                         for p in entry.parents)
            out = self.pool.get().launch(
                self._chunked_fn(region, rem, args), *args,
                family=region.kernel, bucket=rem)
            recs.append(_ShardLaunch(region, entry, out, n_even, rem,
                                     region.waves))
            hist = region.stats["aggregated_hist"]
            for b in greedy_decomposition(rem, region.ladder):
                hist[b] = hist.get(b, 0) + 1
                self.stats["aggregated_hist"][b] = \
                    self.stats["aggregated_hist"].get(b, 0) + 1
            region.stats["launches"] += 1
            region.stats["remainder_launches"] += 1
            self.stats["launches"] += 1
            occupancy[0] += rem       # remainder runs on the default device
        return recs

    # -- settle: injection, guard, fulfilment ------------------------------
    def _settle(self, rec: _ShardLaunch) -> None:
        region, entry, out = rec.region, rec.entry, rec.out
        fut = entry.future
        poisoned: Dict[int, str] = {}
        if self._injector is not None:
            wave_ids = [entry.wave_base + rec.off + i for i in range(rec.n)]
            hits = self._injector.poison_positions(region.kernel,
                                                   rec.wave, wave_ids)
            if hits:
                base = entry.wave_base + rec.off
                poisoned = {wid - base: mode for wid, mode in hits.items()}
                out = poison_slots(out, sorted(poisoned), poisoned)
                region.stats["faults"]["injected"] += len(poisoned)
        if self._guard == "finite":
            bad = self._nonfinite_rows(out, rec.n)
            if bad:
                region.stats["faults"]["trips"] += 1
                region.stats["faults"]["isolated"] += len(bad)
                self._fulfil_with_failures(rec, out, bad)
                return
        fut._fulfil_range(out, 0, rec.off, rec.n)
        if entry.singles is not None:
            self._fan_out_singles(entry, out, rec.off, rec.n)

    def _nonfinite_rows(self, out: Any, n: int) -> List[int]:
        ok = np.ones(n, bool)
        for leaf in jax.tree_util.tree_leaves(out):
            if not jnp.issubdtype(leaf.dtype, jnp.inexact):
                continue
            flat = leaf.reshape(n, -1) if leaf.ndim > 1 else leaf[:, None]
            ok &= np.asarray(jnp.all(jnp.isfinite(flat), axis=1))
        return [int(i) for i in np.nonzero(~ok)[0]]

    def _fulfil_with_failures(self, rec: _ShardLaunch, out: Any,
                              bad: List[int]) -> None:
        """Per-task containment without re-execution: the guard mask names
        the offending rows exactly (the sharded drain has no cross-task
        coupling to hide behind), so survivors fulfil as contiguous runs
        into the SAME launch output and the bad rows fail individually."""
        region, entry = rec.region, rec.entry
        fut = entry.future
        bad_set = set(bad)
        for i in bad:
            wave_id = entry.wave_base + rec.off + i
            err = TaskFailedError(
                f"non-finite output in family {region.kernel!r} "
                f"(sharded wave {rec.wave}, task {wave_id})",
                task_ids=[wave_id], kernel=region.kernel)
            fut._fail_range(rec.off + i, 1, err)
            if entry.singles is not None:
                entry.singles[rec.off + i]._fail(err)
        run_start = None
        for i in range(rec.n + 1):
            if i < rec.n and i not in bad_set:
                if run_start is None:
                    run_start = i
                continue
            if run_start is not None:
                fut._fulfil_range(out, run_start, rec.off + run_start,
                                  i - run_start)
                if entry.singles is not None:
                    for j in range(run_start, i):
                        entry.singles[rec.off + j]._fulfil(out, j)
                run_start = None

    def _fan_out_singles(self, entry: _ShardPending, out: Any, off: int,
                         n: int) -> None:
        for i in range(n):
            entry.singles[off + i]._fulfil(out, i)

    # -- collective boundaries ---------------------------------------------
    def ghost_gather(self, x: Any) -> Any:
        """Replicate a (possibly) sharded wave output — the ONE all-gather
        at the assemble/finalize boundary.  Already-replicated or
        single-device leaves pass through untouched (the 1-shard mesh makes
        this an identity, so single-device runs pay nothing)."""
        if self.n_shards == 1:
            return x

        def one(leaf):
            sh = getattr(leaf, "sharding", None)
            if isinstance(sh, NamedSharding) and not sh.is_fully_replicated:
                return self._replicate_jit(leaf)
            return leaf

        return jax.tree_util.tree_map(one, x)

    def halo_exchange(self, x: Any, axis_name: str = "data") -> Any:
        """Ring ``ppermute`` along one mesh axis: each shard hands its
        block to the next shard around the ring (the ghost-layer exchange
        primitive for shard-local stencils; identity on a 1-shard axis)."""
        n = self.mesh.shape[axis_name]
        perm = [(i, (i + 1) % n) for i in range(n)]
        spec = P(SUBGRID_AXES)

        def _shift(lx):
            return jax.lax.ppermute(lx, axis_name, perm)

        fn = jax.jit(shard_map(_shift, mesh=self.mesh,
                               in_specs=spec, out_specs=spec))
        return fn(jax.device_put(x, self._sharding))

    # -- warmup / tuning surface (runner protocol) -------------------------
    def warmup(self, example_args: Optional[Tuple[Any, ...]] = None, *,
               kernel: Optional[str] = None,
               parent_shapes: Optional[Sequence[Any]] = None,
               buckets: Optional[Sequence[int]] = None,
               store: Optional[Any] = None) -> None:
        """Pre-compile the wave programs a full range submission will hit
        (the sharded shard_map drain + the remainder program) by running a
        throwaway wave of ones.  ``buckets``/``store`` are accepted for
        runner-protocol parity; the sharded ladder has no measured state
        to restore (tuning is keyed per topology — satellite of §13)."""
        if parent_shapes is None:
            return
        kernel = self._resolve_kernel(kernel)
        parents = tuple(jnp.ones(tuple(p.shape), p.dtype)
                        for p in parent_shapes)
        guard, injector = self._guard, self._injector
        self._guard, self._injector = "off", None
        try:
            fut = self.submit_range(parents, 0, parents[0].shape[0],
                                    kernel=kernel)
            self.flush()
            jax.block_until_ready(jax.tree_util.tree_leaves(fut.result()))
        finally:
            self._guard, self._injector = guard, injector

    def save_tuning(self, store: Optional[Any] = None) -> Optional[str]:
        return None                     # no measured state to persist

    def breaker_state(self, kernel: str) -> str:
        return "closed"

    def breaker_states(self) -> Dict[str, str]:
        return {r.kernel: "closed" for r in self._regions.values()}


class _Tenant:
    __slots__ = ("tid", "scenario", "state", "dt", "steps")

    def __init__(self, tid, scenario, state, dt):
        self.tid = tid
        self.scenario = scenario
        self.state = state
        self.dt = dt
        self.steps = 0


class TenantBatcher:
    """Funnel MANY independent scenario instances through ONE sharded
    executor's waves (DESIGN.md §15).

    Each tenant owns a scenario + state + dt.  ``rk3_step_all`` advances
    every tenant one RK3 step: per Shu-Osher stage, every tenant's
    populations are grouped BY KERNEL FAMILY and concatenated along the
    task axis into one mega-range per family — one sharded wave serves
    hundreds of users.  Slicing the wave output back per tenant happens
    after ``ghost_gather``, and per-task results are bit-identical to each
    tenant running alone (same bodies, no padding; only the bucket
    decomposition changes, which the §2 invariant makes value-neutral).

    ``queue_depths`` / ``shard_occupancy`` are the observability surface
    ``ServingEngine.healthz`` republishes under its ``tenants`` field.
    """

    def __init__(self, executor: ShardedAggregationExecutor):
        self.executor = executor
        self._tenants: Dict[Any, _Tenant] = {}
        self.stats: Dict[str, Any] = {"waves": 0, "merged_tasks": 0,
                                      "max_tenancy": 0}
        self._eager = False              # set when a scenario won't trace
        self._stage_cache: Dict[Any, Dict[str, Any]] = {}

    # -- tenancy lifecycle -------------------------------------------------
    def add(self, tid, scenario, state, dt) -> None:
        if tid in self._tenants:
            raise ValueError(f"tenant {tid!r} already registered")
        for fam in scenario.families():
            self.executor.register(fam.kernel, fam.batched_body)
        self._tenants[tid] = _Tenant(tid, scenario, state, dt)
        self.stats["max_tenancy"] = max(self.stats["max_tenancy"],
                                        len(self._tenants))

    def remove(self, tid):
        return self._tenants.pop(tid).state

    def __len__(self) -> int:
        return len(self._tenants)

    def states(self) -> Dict[Any, Any]:
        return {tid: t.state for tid, t in self._tenants.items()}

    # -- observability -----------------------------------------------------
    def queue_depths(self) -> Dict[Any, int]:
        """Tasks each tenant will contribute to the next wave."""
        return {tid: sum(p.n_tasks
                         for p in t.scenario.populations(t.state))
                for tid, t in self._tenants.items()}

    def shard_occupancy(self) -> List[int]:
        return list(self.executor.stats.get("shard_occupancy", []))

    def healthz(self) -> Dict[str, Any]:
        return {"count": len(self._tenants),
                "queue_depth": self.queue_depths(),
                "shard_occupancy": self.shard_occupancy()}

    # -- merged waves ------------------------------------------------------
    def _rhs_all(self, states: Dict[Any, Any]) -> Dict[Any, Any]:
        """One merged submission wave: every tenant's populations grouped
        by kernel into one range per family, drained together.

        Two jitted phases bracket the executor launch — ``extract`` (every
        tenant's population parents, concatenated per kernel, ONE
        dispatch) and ``assemble`` (slice the gathered wave back per
        tenant and run each scenario's epilogue, ONE dispatch).  Eager
        per-tenant chains cost O(tenants) host dispatches per stage,
        which at high tenancy dominates the step and erases the merged
        wave's amortization; scenarios whose populations/assemble do not
        trace fall back to the eager path permanently (`_eager` flag).
        The wave structure (kernels, offsets, counts) is burned in at
        trace time and cached per tenant-set/state-shape signature."""
        if not self._eager:
            try:
                stage = self._stage_for(states)
            except (jax.errors.JAXTypeError, jax.errors.JAXIndexError):
                # populations/assemble do not trace; anything else (an
                # out-of-memory or runtime error on the device) surfaces
                self._eager = True
                stage = None
            if stage is not None:
                return self._rhs_all_staged(states, stage)
        return self._rhs_all_eager(states)

    def _rhs_all_eager(self, states: Dict[Any, Any]) -> Dict[Any, Any]:
        exe = self.executor
        pops_by = {tid: self._tenants[tid].scenario.populations(st)
                   for tid, st in states.items()}
        groups: Dict[str, Dict[str, Any]] = {}
        order: List[str] = []
        for tid, pops in pops_by.items():
            for pi, pop in enumerate(pops):
                if not pop.n_tasks:
                    continue
                g = groups.get(pop.kernel)
                if g is None:
                    g = groups[pop.kernel] = {
                        "cols": [[] for _ in pop.parents],
                        "slices": [], "total": 0}
                    order.append(pop.kernel)
                for ci, par in enumerate(pop.parents):
                    g["cols"][ci].append(par)
                g["slices"].append((tid, pi, g["total"], pop.n_tasks))
                g["total"] += pop.n_tasks
        futs = {}
        for kernel in order:
            g = groups[kernel]
            parents = tuple(col[0] if len(col) == 1 else jnp.concatenate(col)
                            for col in g["cols"])
            futs[kernel] = exe.submit_range(parents, 0, g["total"],
                                            kernel=kernel)
            self.stats["merged_tasks"] += g["total"]
        exe.flush()
        self.stats["waves"] += 1
        outs: Dict[Tuple[Any, int], Any] = {}
        for kernel in order:
            g = groups[kernel]
            try:
                batch = exe.ghost_gather(gather_futures([futs[kernel]]))
            except TaskFailedError as err:
                raise TaskFailedError(
                    f"{self._describe_failed(g, err.task_ids)} failed in "
                    f"merged tenant wave: {err}",
                    task_ids=err.task_ids, kernel=kernel) from err
            for tid, pi, off, n in g["slices"]:
                if off == 0 and n == g["total"]:
                    outs[(tid, pi)] = batch       # sole contributor
                else:
                    outs[(tid, pi)] = jax.tree_util.tree_map(
                        lambda x: jax.lax.slice_in_dim(x, off, off + n,
                                                       axis=0), batch)
        result = {}
        for tid, st in states.items():
            scn = self._tenants[tid].scenario
            tenant_outs = []
            for pi, pop in enumerate(pops_by[tid]):
                if (tid, pi) in outs:
                    tenant_outs.append(outs[(tid, pi)])
                else:
                    spec = jax.eval_shape(scn.family(pop.kernel).batched_body,
                                          *pop.parents)
                    tenant_outs.append(jnp.zeros(spec.shape, spec.dtype))
            result[tid] = scn.assemble(st, tenant_outs)
        return result

    # -- staged (jitted) wave path ----------------------------------------
    def _stage_key(self, states: Dict[Any, Any]):
        parts = []
        for tid, st in states.items():
            leaves = jax.tree_util.tree_leaves(st)
            parts.append((tid, tuple((tuple(l.shape), str(l.dtype))
                                     for l in leaves)))
        return tuple(parts)

    def _stage_for(self, states: Dict[Any, Any]) -> Dict[str, Any]:
        key = self._stage_key(states)
        stage = self._stage_cache.get(key)
        if stage is None:
            stage = self._stage_cache[key] = self._build_stage(states)
        return stage

    def _build_stage(self, states: Dict[Any, Any]) -> Dict[str, Any]:
        """Burn the wave structure into two jitted phases.

        A dry eager pass over ``populations`` fixes the per-kernel layout
        (which tenant contributes which slice at which offset); the
        closures below then re-derive the SAME parents/epilogues under
        trace, so offsets and counts are static and each phase compiles
        to one program.  Traceability of every scenario's
        populations/assemble is validated up front with ``eval_shape`` —
        any failure raises here, before a single launch, and `_rhs_all`
        routes the batcher to the eager path for good."""
        tenants = dict(self._tenants)
        pops_by = {tid: tenants[tid].scenario.populations(st)
                   for tid, st in states.items()}
        groups: Dict[str, Dict[str, Any]] = {}
        order: List[str] = []
        for tid, pops in pops_by.items():
            for pi, pop in enumerate(pops):
                if not pop.n_tasks:
                    continue
                g = groups.get(pop.kernel)
                if g is None:
                    g = groups[pop.kernel] = {"slices": [], "total": 0}
                    order.append(pop.kernel)
                g["slices"].append((tid, pi, g["total"], pop.n_tasks))
                g["total"] += pop.n_tasks
        zero_specs = {(tid, pi): jax.eval_shape(
                          tenants[tid].scenario.family(pop.kernel)
                          .batched_body, *pop.parents)
                      for tid, pops in pops_by.items()
                      for pi, pop in enumerate(pops) if not pop.n_tasks}
        n_pops = {tid: len(pops) for tid, pops in pops_by.items()}
        contrib = {(tid, pi): (kernel, off, n)
                   for kernel in order
                   for tid, pi, off, n in groups[kernel]["slices"]}

        def extract(states):
            pb = {tid: tenants[tid].scenario.populations(st)
                  for tid, st in states.items()}
            out = {}
            for kernel in order:
                sl = groups[kernel]["slices"]
                cols = zip(*[pb[tid][pi].parents for tid, pi, _, _ in sl])
                out[kernel] = tuple(
                    col[0] if len(col) == 1 else jnp.concatenate(col)
                    for col in (tuple(c) for c in cols))
            return out

        def assemble(states, batches):
            result = {}
            for tid, st in states.items():
                scn = tenants[tid].scenario
                touts = []
                for pi in range(n_pops[tid]):
                    hit = contrib.get((tid, pi))
                    if hit is None:
                        spec = zero_specs[(tid, pi)]
                        touts.append(jnp.zeros(spec.shape, spec.dtype))
                        continue
                    kernel, off, n = hit
                    batch = batches[kernel]
                    if off == 0 and n == groups[kernel]["total"]:
                        touts.append(batch)
                    else:
                        touts.append(jax.tree_util.tree_map(
                            lambda x, off=off, n=n: jax.lax.slice_in_dim(
                                x, off, off + n, axis=0), batch))
                result[tid] = scn.assemble(st, touts)
            return result

        col_specs = jax.eval_shape(extract, states)
        batch_specs = {}
        for kernel in order:
            tid0 = groups[kernel]["slices"][0][0]
            body = tenants[tid0].scenario.family(kernel).batched_body
            batch_specs[kernel] = jax.eval_shape(
                lambda *p, body=body: body(*p), *col_specs[kernel])
        jax.eval_shape(assemble, states, batch_specs)
        return {"extract": jax.jit(extract), "assemble": jax.jit(assemble),
                "meta": {"order": order, "groups": groups}}

    def _rhs_all_staged(self, states: Dict[Any, Any],
                        stage: Dict[str, Any]) -> Dict[Any, Any]:
        exe = self.executor
        meta = stage["meta"]
        cols = stage["extract"](states)
        futs = {}
        for kernel in meta["order"]:
            g = meta["groups"][kernel]
            futs[kernel] = exe.submit_range(cols[kernel], 0, g["total"],
                                           kernel=kernel)
            self.stats["merged_tasks"] += g["total"]
        exe.flush()
        self.stats["waves"] += 1
        batches = {}
        for kernel in meta["order"]:
            g = meta["groups"][kernel]
            try:
                batches[kernel] = exe.ghost_gather(
                    gather_futures([futs[kernel]]))
            except TaskFailedError as err:
                raise TaskFailedError(
                    f"{self._describe_failed(g, err.task_ids)} failed in "
                    f"merged tenant wave: {err}",
                    task_ids=err.task_ids, kernel=kernel) from err
        return stage["assemble"](states, batches)

    def _describe_failed(self, group: Dict[str, Any],
                         task_ids: Sequence[int]) -> str:
        """Translate merged-range task ids into tenant vocabulary."""
        names = []
        for gid in task_ids:
            for tid, pi, off, n in group["slices"]:
                if off <= gid < off + n:
                    names.append(f"tenant {tid!r} task {gid - off}")
                    break
            else:
                names.append(f"task {gid}")
        return ", ".join(names) or "unknown task"

    def rk3_step_all(self) -> Dict[Any, Any]:
        """Advance EVERY tenant one RK3 step (the runner's exact Shu-Osher
        coefficients, so a tenant's trajectory matches a solo run bit for
        bit) — three merged waves total, regardless of tenant count.

        The stage combiners run as a handful of whole-tenancy jitted
        dict-ops (`_scale_all`/`_add_all` below) instead of per-tenant
        eager arithmetic: eager chains cost O(tenants) tiny dispatches
        per stage, which at high tenancy dominates the step and defeats
        the amortization the merged waves bought.  Each multiply and the
        add that consumes it live in SEPARATE jitted programs — inside
        one program XLA:CPU's LLVM backend contracts mul+add into fma
        (measured: ~1 ulp drift, and `lax.optimization_barrier` does not
        survive fusion), which would break bit-identity with the solo
        runner's op-by-op stepping.  Separate executables cannot fuse,
        so every intermediate rounds exactly as the eager expression
        tree does, and the dispatch count stays O(1) in tenancy.
        """
        tids = list(self._tenants)
        dts = {tid: t.dt for tid, t in self._tenants.items()}
        c14, c34 = ({t: 0.25 for t in tids}, {t: 0.75 for t in tids})
        c13 = {t: 1.0 / 3.0 for t in tids}
        c23 = {t: 2.0 / 3.0 for t in tids}
        s0 = self.states()
        l0 = self._rhs_all(s0)
        u1 = _add_all(s0, _scale_all(l0, dts))          # u + dt*l0
        l1 = self._rhs_all(u1)
        m1 = _add_all(u1, _scale_all(l1, dts))          # u1 + dt*l1
        u2 = _add_all(_scale_all(s0, c34), _scale_all(m1, c14))
        l2 = self._rhs_all(u2)
        m2 = _add_all(u2, _scale_all(l2, dts))          # u2 + dt*l2
        out = _add_all(_scale_all(s0, c13), _scale_all(m2, c23))
        for tid, t in self._tenants.items():
            t.state = t.scenario.finalize_step(out[tid])
            t.steps += 1
        return self.states()


# -- whole-tenancy RK3 stage combiners (O(1) dispatches per stage) ----------
# Module-level jits so every TenantBatcher shares the compiled programs
# (keyed by tenant-dict structure + leaf shapes).  Each tenant's leaves
# compute the runner's exact Shu-Osher expression trees; batching tenants
# into one program changes dispatch count only, never arithmetic.  The
# scale and add halves of each axpy are DELIBERATELY separate jits: fused
# into one program, XLA:CPU contracts mul+add into fma (~1 ulp drift vs
# the solo runner's eager op-by-op stepping), and optimization_barrier
# does not survive fusion — separate executables are the only reliable
# contraction fence.

@jax.jit
def _scale_all(xs, cs):
    """{tid: c_tid * x} — per-tenant scalar scale, one dispatch."""
    tm = jax.tree_util.tree_map
    return {tid: tm(lambda x, c=cs[tid]: c * x, xs[tid]) for tid in xs}


@jax.jit
def _add_all(xs, ys):
    """{tid: x + y} — elementwise add across the tenancy, one dispatch."""
    tm = jax.tree_util.tree_map
    return {tid: tm(jnp.add, xs[tid], ys[tid]) for tid in xs}
