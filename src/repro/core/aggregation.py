"""The paper's strategy 3: on-the-fly explicit work aggregation, TPU-native.

Fine-grained tasks submit "launch kernel K on my inputs" requests.  While the
underlying executor is busy, compatible submissions accumulate; when it
becomes idle — or the ``max_aggregated`` cap is reached — the queued tasks
are fused into ONE batched kernel launch over a slot axis.  Each task gets a
future resolving to its slot of the batched output.

Multi-region runtime (DESIGN.md §7): one executor hosts MANY aggregation
regions at once.  Submissions are routed by :class:`TaskSignature` — kernel
id plus per-argument shape/dtype — to their family's slot ring, queue and
compiled-bucket cache, so heterogeneous task populations (the adaptive-
refinement regime of the follow-up AMR work, arXiv:2412.15518) aggregate
concurrently without serializing each other.  A region is created lazily the
first time a signature is seen, which also makes a single registered kernel
shape-polymorphic: new task shapes simply open new regions over the same
body.

TPU adaptation (DESIGN.md §2): XLA requires static shapes, so a dynamic
aggregation count is realized as a small set of pre-compiled *buckets*
(powers of two up to the cap).  A queue of length k is drained greedily with
the largest bucket <= k; because bucket 1 exists, no padding is ever needed
and results are *bit-identical* to unaggregated execution (the equivalence
invariant tested in tests/test_aggregation.py and tests/test_slot_ring.py).

Staging (DESIGN.md §3): the hot path is device-resident end to end.  Task
inputs either

* land in a pre-allocated :class:`~repro.core.buffers.SlotRing` via donated
  coalesced scatters (concrete per-task arrays), or
* stay where they already live and are referenced by a :class:`SlotView`
  ``(parent, index)``; a launch then performs ONE ``jnp.take`` gather inside
  the bucketed program (index-batched staging, zero per-task slicing).

The seed's slice -> host-stack -> launch cycle survives as
``staging="host"`` so benchmarks/launch_overhead.py can measure the win.

The paper's "Single-GPU-workload-Multiple-Tasks" constraint (all aggregated
tasks execute the same allocation/launch sequence) is enforced *statically*
here: each region's bucketed kernel is one traced function extended over the
slot axis, so divergence between aggregated tasks is impossible by
construction.
"""
from __future__ import annotations

import bisect
import statistics
import threading
import time
import warnings
from dataclasses import dataclass
from functools import partial
from types import MappingProxyType
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (
    AggregationConfig, resolve_family_option, validate_ladder,
)
from repro.core.buffers import DEFAULT_POOL, BufferPool, SlotRing
from repro.core.compile_cache import enable_compile_cache
from repro.core.executor import ExecutorPool
from repro.core.faults import (
    BucketCompileError, FaultInjector, LaunchFaultError, LaunchTimeoutError,
    QuarantineList, RegionFaultError, TaskFailedError, all_finite,
    all_finite_async, poison_slots,
)
from repro.core.trace import named, program_name, span
from repro.core.tunestore import RooflinePrior, TuneStore, TuneStoreWarning


# inner-chunk auto-tune memo: (backend, body id, bucket, task specs) ->
# (body, chunk).  Keyed on the backend AND device kind because the chunk is
# a *measured* choice — a value timed on one backend must never leak into a
# process that later tunes the same body on another device.  Keeping the
# body ref in the value pins its id() for the key's lifetime (an id-keyed
# entry without the ref would collide on id reuse); the cache is
# FIFO-bounded so long-lived sweeps don't pin every body ever tuned.
_CHUNK_TUNE_MEMO: Dict[Tuple, Tuple[Any, int]] = {}
_CHUNK_TUNE_MEMO_MAX = 32


def _backend_key() -> Tuple[str, str, str]:
    """(backend, device kind, device topology) — the identity a timed tuning
    choice is valid for.  Measured decisions (inner_chunk, bucket costs) are
    per-device AND per-topology: what saturates a TPU-v4 is not what
    saturates a 2-core CPU, and a bucket ladder tuned on 1 device must not
    warm-start an 8-device sharded run (the per-shard wave is 8× smaller).
    The topology component is ``d<count>`` so single-device keys stay
    distinct from forced-host-platform multi-device keys."""
    try:
        devs = jax.devices()
        kind = getattr(devs[0], "device_kind", "")
        topo = f"d{len(devs)}"
    except RuntimeError:
        kind, topo = "", "d0"
    return jax.default_backend(), kind, topo


class TaskFuture:
    """HPX-future analogue: resolves to one task's slice of a batched launch.

    Resolution is lazy twice over: ``_fulfil`` only records (batch, slot) —
    no per-slot ``tree_map`` happens until ``result()`` is actually read —
    and callers that want the whole batch back should use
    :func:`gather_futures`, which recognises futures covering a full launch
    and returns the batched output itself with zero copies.

    Under ``guard="finite"`` a future may resolve FAILED instead of to a
    value (DESIGN.md §11): ``failed()`` reports it, ``error()`` carries the
    :class:`~repro.core.faults.TaskFailedError`, and ``result()`` raises it
    — a contained fault never returns garbage.
    """

    __slots__ = ("_value", "_batch", "_slot", "_done", "_error")

    def __init__(self):
        self._value = None
        self._batch = None
        self._slot = -1
        self._done = False
        self._error = None

    def _fulfil(self, batch_out: Any, slot: int) -> None:
        self._batch, self._slot, self._done = batch_out, slot, True

    def _fail(self, err: Exception) -> None:
        self._error, self._done = err, True
        self._batch = self._value = None

    def _retract(self) -> None:
        """Un-fulfil: the launch that fulfilled this future tripped the
        guard; containment will re-fulfil (or fail) it."""
        self._done = False
        self._batch = self._value = None

    def ready(self) -> bool:
        return self._done

    def failed(self) -> bool:
        return self._error is not None

    def error(self) -> Optional[Exception]:
        return self._error

    def result(self) -> Any:
        if self._error is not None:
            raise self._error
        if not self._done:
            raise RuntimeError("task not launched yet — call executor.flush()")
        if self._value is None:
            slot = self._slot
            self._value = jax.tree_util.tree_map(lambda x: x[slot], self._batch)
            self._batch = None
        return self._value


class RangeFuture:
    """One future for a contiguous range of ``count`` tasks (the bulk-
    submission analogue of :class:`TaskFuture`).

    A range enters the queue as ONE entry; the greedy drain may still split
    it across several bucketed launches, so fulfilment is segmented: each
    launch contributes ``(range_offset, batch, slot, n)``.  ``result()``
    assembles the full ``(count, ...)`` batch — zero-copy when one launch
    covered the whole range in order, which is the steady-state fast path
    (``submit_range`` of a full wave -> one mega-bucket launch -> the
    launch output IS the result).

    Containment (DESIGN.md §11) may mark individual offsets of the range
    FAILED: ``failed_indices()`` lists them, ``error(i)`` returns a task's
    :class:`~repro.core.faults.TaskFailedError`, ``task_result(i)`` reads
    one surviving task, and ``result()``/``gather_futures`` raise rather
    than assemble a batch with garbage slots in it.
    """

    __slots__ = ("_parts", "_count", "_value", "_failed")

    def __init__(self, count: int):
        self._parts: List[Tuple[int, Any, int, int]] = []
        self._count = count
        self._value = None
        self._failed: Dict[int, Exception] = {}

    def __len__(self) -> int:
        return self._count

    def _fulfil_range(self, batch_out: Any, slot: int, offset: int,
                      n: int) -> None:
        self._parts.append((offset, batch_out, slot, n))

    def _fail_range(self, offset: int, n: int, err: Exception) -> None:
        for i in range(offset, offset + n):
            self._failed[i] = err

    def _retract(self, batch_out: Any) -> None:
        """Drop every segment a tripped launch contributed (containment
        re-fulfils or fails those offsets after bisection)."""
        self._parts = [p for p in self._parts if p[1] is not batch_out]

    def ready(self) -> bool:
        if self._value is not None:     # resolved (parts were released)
            return True
        return (sum(p[3] for p in self._parts) + len(self._failed)
                == self._count)

    def failed(self) -> bool:
        return bool(self._failed)

    def failed_indices(self) -> List[int]:
        return sorted(self._failed)

    def error(self, index: Optional[int] = None) -> Optional[Exception]:
        if index is not None:
            return self._failed.get(index)
        return next(iter(self._failed.values()), None)

    def result(self) -> Any:
        """The whole range as one batched pytree (task axis leading)."""
        if self._failed:
            raise TaskFailedError(
                f"{len(self._failed)} of {self._count} tasks in this range "
                f"failed (indices {self.failed_indices()}) — read survivors "
                f"individually with task_result()",
                task_ids=self.failed_indices())
        if self._value is None:
            if not self.ready():
                raise RuntimeError(
                    "range not fully launched yet — call executor.flush()")
            self._value = _assemble_segments(
                [(batch, slot, n)
                 for _, batch, slot, n in sorted(self._parts,
                                                 key=lambda p: p[0])])
            self._parts = []
        return self._value

    def task_result(self, index: int) -> Any:
        """One task's result (raises its error if containment failed it)."""
        if index in self._failed:
            raise self._failed[index]
        if not 0 <= index < self._count:
            raise IndexError(f"task {index} out of range [0, {self._count})")
        if self._value is not None:
            return jax.tree_util.tree_map(lambda x: x[index], self._value)
        for off, batch, slot, n in self._parts:
            if off <= index < off + n:
                i = slot + (index - off)
                return jax.tree_util.tree_map(lambda x: x[i], batch)
        raise RuntimeError("task not launched yet — call executor.flush()")

    def _segments(self):
        if self._failed:
            raise TaskFailedError(
                f"range contains {len(self._failed)} failed tasks "
                f"(indices {self.failed_indices()}) — gather_futures would "
                f"assemble garbage slots; read survivors with task_result()",
                task_ids=self.failed_indices())
        if self._value is not None:
            leaves = jax.tree_util.tree_leaves(self._value)
            yield self._value, 0, leaves[0].shape[0]
            return
        if not self.ready():
            raise RuntimeError(
                "range not fully launched yet — call executor.flush()")
        for _, batch, slot, n in sorted(self._parts, key=lambda p: p[0]):
            yield batch, slot, n


def _assemble_segments(segments: List[Tuple[Any, int, int]]) -> Any:
    """Merge ``(batch, start_slot, n)`` runs into one batched pytree.

    Consecutive runs on the same launch output coalesce; a run covering a
    whole launch in order contributes the batch itself (zero-copy), a
    contiguous partial run is one slice, anything else one ``jnp.take``.
    """
    parts = []
    i = 0
    while i < len(segments):
        batch = segments[i][0]
        runs = []                                  # [(start, n)] on `batch`
        while i < len(segments) and segments[i][0] is batch:
            s0, n = segments[i][1], segments[i][2]
            if runs and runs[-1][0] + runs[-1][1] == s0:
                runs[-1] = (runs[-1][0], runs[-1][1] + n)
            else:
                runs.append((s0, n))
            i += 1
        n_slots = jax.tree_util.tree_leaves(batch)[0].shape[0]
        if runs == [(0, n_slots)]:
            parts.append(batch)       # the whole launch, in order: zero-copy
        elif len(runs) == 1:
            s0, n = runs[0]
            parts.append(jax.tree_util.tree_map(
                lambda x: jax.lax.slice_in_dim(x, s0, s0 + n, axis=0), batch))
        else:
            idx = jnp.asarray([s for s0, n in runs
                               for s in range(s0, s0 + n)], jnp.int32)
            parts.append(jax.tree_util.tree_map(
                lambda x: jnp.take(x, idx, axis=0), batch))
    if len(parts) == 1:
        return parts[0]
    return _concat_parts(parts)


def _concat_parts(parts: List[Any]) -> Any:
    task_specs = {tuple((tuple(x.shape[1:]), np.dtype(x.dtype).str)
                        for x in jax.tree_util.tree_leaves(p))
                  for p in parts}
    if len(task_specs) > 1:
        raise ValueError(
            f"futures span task families with different output "
            f"shapes/dtypes {sorted(task_specs)} — gather each family "
            f"separately")
    return jax.tree_util.tree_map(lambda *xs: jnp.concatenate(xs), *parts)


def gather_futures(futs: Sequence[Any]) -> Any:
    """Assemble many futures' results into one batched array, lazily.

    Futures fulfilled by the same launch share one batched output; a run of
    such futures in slot order contributes the batch itself (zero-copy).
    Out-of-order runs become a single ``jnp.take``; distinct launches are
    joined with one ``jnp.concatenate``.  This replaces the seed's
    per-future slice + re-stack (2n device ops for n tasks) with O(launches)
    ops.

    ``TaskFuture`` and ``RangeFuture`` entries may be interleaved freely (a
    range contributes its launch segments in range order), as may launches
    from different regions — but all results must share one output
    task-shape to concatenate; gather each family separately otherwise.
    """
    if not futs:
        raise ValueError("gather_futures needs at least one future")
    segments: List[Tuple[Any, int, int]] = []
    parts = []

    def emit_segments():
        if segments:
            parts.append(_assemble_segments(segments))
            segments.clear()

    for f in futs:
        if isinstance(f, RangeFuture):
            segments.extend(f._segments())
            continue
        if f._error is not None:      # a failed task never assembles
            raise f._error
        if not f._done:
            raise RuntimeError("task not launched yet — call executor.flush()")
        if f._batch is None:          # already resolved individually
            emit_segments()
            parts.append(jax.tree_util.tree_map(lambda x: x[None], f.result()))
        else:
            segments.append((f._batch, f._slot, 1))
    emit_segments()
    if len(parts) == 1:
        return parts[0]
    return _concat_parts(parts)


class SlotView:
    """Zero-copy task-input reference: ``parent[index]``, never sliced.

    Submitting SlotViews lets ``_launch`` stage a whole bucket with ONE
    ``jnp.take`` over the already-device-resident parent instead of n
    per-task slices — the index-batched staging mode.
    """

    __slots__ = ("parent", "index")

    def __init__(self, parent: jax.Array, index: int):
        self.parent = parent
        self.index = index


def _spec_of(a) -> Tuple[Tuple[int, ...], str]:
    """(shape, dtype-str) of one task argument (SlotView -> per-slot spec)."""
    if isinstance(a, SlotView):
        p = a.parent
        return tuple(p.shape[1:]), np.dtype(p.dtype).str
    if hasattr(a, "shape") and hasattr(a, "dtype"):   # jax array / SDS
        return tuple(a.shape), np.dtype(a.dtype).str
    arr = np.asarray(a)
    return arr.shape, np.dtype(jax.dtypes.canonicalize_dtype(arr.dtype)).str


@dataclass(frozen=True)
class TaskSignature:
    """What makes two fine-grained tasks aggregable: the kernel family id
    plus every argument's per-task shape and dtype.  The paper's SGMT
    compatibility check, reified as the region-registry key."""

    kernel: str
    arg_specs: Tuple[Tuple[Tuple[int, ...], str], ...]

    @classmethod
    def from_args(cls, kernel: str, args: Sequence[Any]) -> "TaskSignature":
        return cls(kernel, tuple(_spec_of(a) for a in args))

    def describe(self) -> str:
        """Unique human-readable key: shapes, with dtype appended whenever
        it is not the default float32 (so same-shape families of different
        dtypes never collide in ``stats["regions"]``)."""
        f32 = np.dtype(np.float32).str

        def one(spec):
            shape, dt = spec
            s = "x".join(map(str, shape)) or "scalar"
            return s if dt == f32 else f"{s}:{dt.lstrip('<>|=')}"

        return f"{self.kernel}[{','.join(one(s) for s in self.arg_specs)}]"


@dataclass
class _Pending:
    future: Any                                   # TaskFuture | RangeFuture
    slot: int = -1                               # ring mode: slot in the ring
    views: Optional[Tuple[SlotView, ...]] = None  # ref mode
    args: Optional[Tuple[Any, ...]] = None        # host mode
    count: int = 1                    # tasks in this entry (>1: slot range)
    fut_offset: int = 0               # this entry's offset in its RangeFuture
    wave_index: int = 0               # first task's wave-relative id (§11)

    def split(self, n: int) -> Tuple["_Pending", "_Pending"]:
        """Split a contiguous range entry: first ``n`` tasks / the rest.
        Both halves share the future (each fulfils its own offset)."""
        assert 0 < n < self.count and self.views is not None
        head = _Pending(future=self.future, views=self.views, count=n,
                        fut_offset=self.fut_offset,
                        wave_index=self.wave_index)
        tail = _Pending(
            future=self.future,
            views=tuple(SlotView(v.parent, v.index + n) for v in self.views),
            count=self.count - n, fut_offset=self.fut_offset + n,
            wave_index=self.wave_index + n)
        return head, tail


@dataclass
class _LaunchRecord:
    """Everything the post-drain guard needs to audit ONE launch and, on a
    trip, re-execute arbitrary slot subsets of it (DESIGN.md §11).

    ``parents`` + ``indices`` are the re-execution recipe: whatever the
    staging mode was, subset ``S`` re-runs as
    ``region.compiled_for(len(S), "gather")(indices[S], *parents)`` — for
    ref staging the parents are the submitted parent arrays, for ring
    staging the launched ring buffers (held by reference, so a post-launch
    ``swap`` cannot invalidate them), for host staging the stacked input
    batch itself.
    ``poisoned`` records which wave-relative task ids carried an injected
    payload fault at launch time; re-executions re-apply exactly those (the
    poison is a property of the TASK, so bisection converges on it)."""

    region: "_Region"
    out: Any                          # the launch's batched output
    k: int                            # bucket size
    parents: Tuple[Any, ...]          # arrays the gather re-executes against
    indices: List[int]                # per-position absolute parent index
    tasks: List["_Pending"]           # the entries this launch fulfilled
    wave_ids: List[int]               # per-position wave-relative task id
    wave: int                         # region wave counter at launch
    poisoned: Dict[int, str]          # wave id -> injected payload mode
    verdict: Any = True               # in-flight all-finite device scalar,
                                      # dispatched at launch, forced at flush


def _split_taken(entries: List[_Pending], n: int
                 ) -> Tuple[List[_Pending], List[_Pending]]:
    """Split an already-TAKEN entry list at task boundary ``n`` (degraded
    re-draining: the queue bookkeeping was done by ``_take``, only the
    entries themselves still need carving to the smaller bucket)."""
    head: List[_Pending] = []
    rest = list(entries)
    need = n
    while need:
        e = rest[0]
        if e.count <= need:
            head.append(rest.pop(0))
            need -= e.count
        else:
            h, t = e.split(need)
            rest[0] = t
            head.append(h)
            need = 0
    return head, rest


class BucketCostModel:
    """Measured per-bucket wall times for ONE region (DESIGN.md §10).

    ``record`` accumulates raw timed samples per bucket size; ``time``
    reports the median (robust against scheduler hiccups on a noisy host);
    ``predict`` extends the table to unmeasured sizes by piecewise-linear
    interpolation in the bucket size — clamped below the smallest measured
    bucket (a launch never costs less than the smallest thing we timed,
    which is what stops the tuner from hallucinating free micro-launches)
    and extrapolated above the largest with the last measured segment's
    slope (floored at the largest measurement).

    The model is the common currency of the measured tuner: the ladder
    derivation minimizes ``predict_seq`` of each wave's greedy
    decomposition, and the ``"cost"`` flush policy compares split-drain
    against one-shot predictions.  ``as_stats`` is the JSON-safe table
    persisted into ``stats["regions"][fam]["cost_model"]`` and the BENCH
    rows (milliseconds, bucket-keyed).

    Execution paths (DESIGN.md §12): every method takes an optional
    ``path``.  The default ``"s3"`` table holds the bucketed-program
    timings above; ``"s2"`` holds per-launch times of the donated
    scatter-ring program keyed by coalesce WIDTH, and ``"fused"`` holds
    the one-launch whole-wave body keyed by wave size — so
    ``select_strategy`` compares all three strategies' measured wall
    times in one currency.

    Priors (DESIGN.md §13): ``seed_prior`` installs an ANALYTICAL
    estimate (the tunestore's :class:`RooflinePrior`) in a separate
    per-path table.  Measured samples always win: ``predict`` only
    consults a path's priors when that path has zero real samples, and
    counts every such consultation in ``prior_hits`` (the observability
    hook for "this decision ran on arithmetic, not a stopwatch").
    ``sources()`` labels every known bucket ``"measured" | "store" |
    "prior"`` so the stats surface can show where a table came from.
    """

    __slots__ = ("samples", "_paths", "priors", "_sources", "prior_hits")

    def __init__(self):
        self.samples: Dict[int, List[float]] = {}
        # path -> {bucket/width: raw samples}; "s3" aliases ``samples``
        # so the historical single-table surface keeps working unchanged
        self._paths: Dict[str, Dict[int, List[float]]] = {"s3": self.samples}
        self.priors: Dict[str, Dict[int, float]] = {}
        self._sources: Dict[Tuple[str, int], str] = {}
        self.prior_hits = 0

    def _table(self, path: str) -> Dict[int, List[float]]:
        t = self._paths.get(path)
        if t is None:
            t = self._paths[path] = {}
        return t

    def record(self, bucket: int, seconds: float, path: str = "s3",
               source: str = "measured") -> None:
        self._table(path).setdefault(int(bucket), []).append(float(seconds))
        self._sources[(path, int(bucket))] = source

    def seed_prior(self, bucket: int, seconds: float,
                   path: str = "s3") -> None:
        """Install an analytical estimate for one bucket.  Lives beside
        the sample tables, never in them — a prior must not suppress the
        real measurement of its bucket (``time`` stays None)."""
        self.priors.setdefault(path, {})[int(bucket)] = float(seconds)

    def clear(self) -> None:
        """Drop every sample on every path (the measurements' premise
        changed — e.g. the region's inner chunk was re-swept, so old
        timings describe programs that no longer exist)."""
        for table in self._paths.values():
            table.clear()
        self.priors.clear()
        self._sources.clear()

    def clear_priors(self) -> None:
        """Retire the analytical seeds (retune just measured for real —
        the §13 'fully replaced by measurements' contract)."""
        self.priors.clear()

    def measured(self, path: str = "s3") -> bool:
        return bool(self._paths.get(path))

    def seeded(self, path: str = "s3") -> bool:
        return bool(self.priors.get(path))

    def has_data(self, path: str = "s3") -> bool:
        """Can ``predict`` answer for this path (measured or seeded)?"""
        return self.measured(path) or self.seeded(path)

    def sources(self) -> Dict[str, Dict[int, str]]:
        """{path: {bucket: "measured" | "store" | "prior"}} — where each
        known bucket's number came from (priors shadowed by samples)."""
        out: Dict[str, Dict[int, str]] = {}
        for path, prior in self.priors.items():
            for b in prior:
                out.setdefault(path, {})[b] = "prior"
        for (path, b), src in self._sources.items():
            if self._paths.get(path, {}).get(b):
                out.setdefault(path, {})[b] = src
        return out

    def paths(self) -> Tuple[str, ...]:
        """The execution paths with at least one measurement."""
        return tuple(sorted(p for p, t in self._paths.items() if t))

    def buckets(self, path: str = "s3") -> Tuple[int, ...]:
        return tuple(sorted(self._paths.get(path, ())))

    def time(self, bucket: int, path: str = "s3") -> Optional[float]:
        s = self._paths.get(path, {}).get(bucket)
        return statistics.median(s) if s else None

    @staticmethod
    def _interp(bs: Sequence[int], val: Callable[[int], float],
                bucket: int) -> float:
        """Piecewise-linear table extension shared by the measured and
        prior paths: clamp below the smallest entry, interpolate inside,
        extrapolate above with the last segment's slope (floored)."""
        if bucket <= bs[0]:
            return val(bs[0])
        if bucket >= bs[-1]:
            hi = val(bs[-1])
            if len(bs) == 1:
                return hi * bucket / bs[-1]
            lo = val(bs[-2])
            slope = (hi - lo) / (bs[-1] - bs[-2])
            return max(hi, hi + slope * (bucket - bs[-1]))
        i = bisect.bisect_left(bs, bucket)
        b0, b1 = bs[i - 1], bs[i]
        t0, t1 = val(b0), val(b1)
        return t0 + (t1 - t0) * (bucket - b0) / (b1 - b0)

    def predict(self, bucket: int, path: str = "s3") -> float:
        t = self.time(bucket, path)
        if t is not None:
            return t
        bs = self.buckets(path)
        if bs:
            return self._interp(bs, lambda b: self.time(b, path), bucket)
        prior = self.priors.get(path)
        if prior:
            # analytical fallback — only ever consulted for a path with
            # ZERO real samples, so one measurement retires a whole table
            self.prior_hits += 1
            pbs = tuple(sorted(prior))
            return self._interp(pbs, prior.__getitem__, bucket)
        raise ValueError("cost model has no measurements or priors — "
                         "check has_data() before predicting")

    def predict_seq(self, buckets: Sequence[int], path: str = "s3") -> float:
        """Predicted wall time of one greedy drain (launch sequence)."""
        return sum(self.predict(b, path) for b in buckets)

    def predict_s2_wave(self, wave: int) -> Optional[Tuple[int, float]]:
        """(best coalesce width, predicted seconds) for scattering a
        ``wave``-task population through the measured s2 widths: each
        width-w launch covers w tasks, the remainder falls back to the
        width-1 program.  None before any "s2" measurement (or when a
        remainder would need an unmeasured width-1 program)."""
        ws = self.buckets("s2") or tuple(sorted(self.priors.get("s2", ())))
        if not ws:
            return None
        best = None
        for w in ws:
            if w > wave:
                continue
            rem = wave % w
            if rem and 1 not in ws:
                continue
            t = (wave // w) * self.predict(w, "s2")
            if rem:
                t += rem * self.predict(1, "s2")
            if best is None or t < best[1]:
                best = (w, t)
        return best

    def as_stats(self, path: str = "s3") -> Dict[int, float]:
        """{bucket: median milliseconds}, rounded for the stats surface."""
        return {b: round(self.time(b, path) * 1e3, 4)
                for b in self.buckets(path)}

    def as_stats_paths(self) -> Dict[str, Dict[int, float]]:
        """Every measured path's table — the DESIGN.md §12 observability
        surface backing per-family strategy selection."""
        return {p: self.as_stats(p) for p in self.paths()}


def greedy_decomposition(k: int, buckets: Sequence[int]) -> Tuple[int, ...]:
    """The bucket sequence the greedy drain launches for a queue of length
    k under a valid ladder (every bucket <= the cap by validation, so this
    models over-cap waves too: a 100-task wave under cap 64 is 64 + the
    greedy cover of 36).  Shared by the launch path, the ladder tuner and
    wave-only warmup — one definition of "what will actually launch"."""
    out = []
    while k:
        b = max(x for x in buckets if x <= k)
        out.append(b)
        k -= b
    return tuple(out)


def greedy_launches(k: int, buckets: Sequence[int]) -> int:
    """Launches the greedy drain performs for a queue of length k under a
    valid ladder (shared oracle; tests mirror it in conftest.py)."""
    return len(greedy_decomposition(k, buckets))


# ---------------------------------------------------------------------------
# s2 scatter-ring programs (DESIGN.md §12) — shared by the ``s2`` strategy,
# the ``mixed`` router and the executor's cost-model measurement pass, so
# the program that gets TIMED is byte-for-byte the program that RUNS.
# ---------------------------------------------------------------------------

def make_s2_scatter(batched_fn: Callable, width: int = 1) -> Callable:
    """One s2 launch: slice ``width`` contiguous tasks out of the parent
    arrays, run the batched body over them, scatter the results into a
    donated output ring — ONE compiled program, zero host staging.  Width
    1 is the paper's implicit aggregation; larger widths coalesce
    neighbouring tasks into one launch (ring sizing driven by the
    measured cost model).  Bit-identity holds for every width: the body
    is elementwise over the slot axis, so a width-w slice computes
    exactly the same values as w width-1 slices."""
    @partial(jax.jit, donate_argnums=(0,))
    def scatter(out_ring, i, *parents):
        task = tuple(jax.lax.dynamic_slice_in_dim(p, i, width, axis=0)
                     for p in parents)
        return jax.lax.dynamic_update_slice(
            out_ring, batched_fn(*task), (i,) + (0,) * (out_ring.ndim - 1))
    return scatter


def s2_width_candidates(wave: int) -> Tuple[int, ...]:
    """The coalesce widths the s2 cost measurement probes: 1 (the classic
    per-task scatter), 2, and the largest power of two fitting the wave.
    Every distinct width is a full XLA compile of the family body, so the
    probe set stays at three points — the endpoints bound the
    per-launch-overhead vs. batch-scaling tradeoff, and width 2 exposes a
    superlinear body (one where coalescing LOSES) without paying for the
    intermediate powers."""
    top = 1
    while top * 2 <= wave:
        top *= 2
    return tuple(sorted({1, min(2, wave), top}))


def measure_s2_widths(batched_fn: Callable, parents: Sequence[Any],
                      widths: Sequence[int], samples: int = 3,
                      cache: Optional[Dict[int, Callable]] = None
                      ) -> Dict[int, float]:
    """Time the donated scatter program per coalesce width on zero-filled
    parents: one warm (compile) call, then the median of ``samples`` timed
    launches each.  Returns {width: seconds per launch}.  ``cache`` (if
    given) receives the compiled scatter fns keyed by width, so a caller
    that will RUN the winning width reuses the warmed program.  Bodies
    whose batched output is not a single array skip measurement (the
    scatter ring is a single donated buffer)."""
    concrete = tuple(jnp.zeros(tuple(p.shape), p.dtype) for p in parents)
    wave = min(p.shape[0] for p in concrete)
    try:
        spec = jax.eval_shape(batched_fn, *concrete)
    except (TypeError, ValueError):
        return {}
    if not hasattr(spec, "shape"):           # pytree output: no single ring
        return {}
    out: Dict[int, float] = {}
    for w in sorted(set(widths)):
        if w > wave:
            continue
        fn = make_s2_scatter(batched_fn, w)
        ring = jnp.zeros(spec.shape, spec.dtype)
        i0 = jnp.int32(0)
        ring = fn(ring, i0, *concrete)                 # compile + warm
        jax.block_until_ready(ring)
        ts = []
        for _ in range(max(1, samples)):
            t0 = time.perf_counter()
            ring = fn(ring, i0, *concrete)
            jax.block_until_ready(ring)
            ts.append(time.perf_counter() - t0)
        out[w] = statistics.median(ts)
        if cache is not None:
            cache[w] = fn
    return out


def ladder_candidates(queue_hist: Mapping[int, int], cap: int) -> set:
    """The bucket sizes a ladder derivation considers: observed wave peaks
    clipped to the cap, their cap-split remainders, plus powers of two up
    to the cap.  Shared by :func:`derive_ladder` and the executor's
    cost-model measurement pass, so exactly the drain-reachable sizes the
    tuner may pick are the ones that get timed."""
    candidates = set()
    for k in queue_hist:
        if k <= 0:
            continue
        candidates.add(min(k, cap))
        if k > cap and k % cap:
            candidates.add(k % cap)   # the cap-split remainder of the wave
    b = 1
    while b <= cap:
        candidates.add(b)
        b *= 2
    return candidates


def derive_ladder(queue_hist: Mapping[int, int], cap: int, budget: int,
                  cost_model: Optional[BucketCostModel] = None
                  ) -> Tuple[int, ...]:
    """Re-derive a bucket ladder from an observed queue-length histogram.

    Starting from the mandatory ``{1}`` (the no-padding invariant needs a
    remainder bucket) seeded with the dominant wave's cap-decomposition
    (a single candidate search cannot learn that the cap bucket is only
    worth having TOGETHER with its remainder — e.g. a 100-task wave under
    cap 64 wants {64, 36} as a pair), greedily add the candidate size
    (:func:`ladder_candidates`) that most reduces the per-wave objective,
    until ``budget`` distinct bucket programs are reached or no candidate
    improves.  A steady k-task wave therefore converges on a ladder
    covering k exactly: one launch per cap-chunk, no ones-drain.

    The objective is *expected launches per wave* — the §9 proxy — unless
    a measured :class:`BucketCostModel` is supplied, in which case it is
    the *predicted wall time per wave* (DESIGN.md §10: the device's cost
    structure, not a launch count).  Under the model, a final prune drops
    any seeded bucket whose removal does not increase predicted time, so
    exact-cost ties always resolve to the smaller compile footprint
    (candidates are also tried smallest-first: an equal-cost pair admits
    the cheaper program).
    """
    # non-positive "wave lengths" carry no drain (and would crash the
    # greedy cover): drop them before they reach the objective
    queue_hist = {k: c for k, c in queue_hist.items() if k > 0}
    candidates = ladder_candidates(queue_hist, cap)
    # prior-seeded models qualify (DESIGN.md §13): an analytical table is
    # still a wall-time objective, which is the whole point of seeding
    use_model = cost_model is not None and cost_model.has_data()

    def cost(ladder):
        # candidate buckets never exceed the cap, so the greedy cover of
        # the FULL wave length models the real drain (cap-splits included)
        ls = sorted(ladder)
        if use_model:
            return sum(c * cost_model.predict_seq(greedy_decomposition(k, ls))
                       for k, c in queue_hist.items())
        return sum(c * greedy_launches(k, ls)
                   for k, c in queue_hist.items())

    ladder = {1}
    peaks = [k for k in queue_hist if k > 0]
    if peaks:
        top = max(peaks, key=lambda k: (queue_hist[k], k))
        seed = {cap, top % cap} if top > cap else {top}
        for b in sorted(seed - {0}, reverse=True):
            if len(ladder) < budget:
                ladder.add(b)

    def grow():
        while len(ladder) < budget:
            best, best_cost = None, cost(ladder)
            for c in sorted(candidates - ladder):
                cc = cost(ladder | {c})
                if cc < best_cost:
                    best, best_cost = c, cc
            if best is None:
                break
            ladder.add(best)

    grow()
    if use_model:
        # The seeds were added without a cost check (correct under the
        # launch-count objective, where a mega bucket can never lose);
        # measured time CAN say a big bucket is pessimal, so drop any
        # bucket whose removal keeps predicted time no worse — ties go to
        # the smaller compile footprint — then let the search refill the
        # freed budget (a pruned cap bucket may have been shadowing its
        # cheaper halves).  (cost, |ladder|) strictly decreases each
        # cycle, so the loop terminates.
        while True:
            pruned = False
            for b in sorted(ladder - {1}, reverse=True):
                if cost(ladder - {b}) <= cost(ladder):
                    ladder.discard(b)
                    pruned = True
                    break
            if not pruned:
                break
            grow()
    return tuple(sorted(ladder))


def _chunked_eval(batched_fn: Callable, chunk: int, *stacked):
    """Mega-bucket evaluation: run the batched body over the slot axis in
    sequential ``chunk``-slot pieces via ONE ``lax.map`` inside the same
    program.  Bit-identical to the flat call (a pure batch split of an
    independent-per-slot body); the win is cache locality — stencil-heavy
    bodies keep their intermediates resident instead of streaming a
    bucket-64-sized working set.  Falls back to the flat call whenever the
    chunk does not divide the bucket (no padding, ever)."""
    k = stacked[0].shape[0] if stacked else 0
    if chunk and 0 < chunk < k and k % chunk == 0:
        resh = tuple(a.reshape((k // chunk, chunk) + a.shape[1:])
                     for a in stacked)
        out = jax.lax.map(lambda xs: batched_fn(*xs), resh)
        return jax.tree_util.tree_map(
            lambda o: o.reshape((o.shape[0] * o.shape[1],) + o.shape[2:]),
            out)
    return batched_fn(*stacked)


class _Region:
    """One aggregation region: per-TaskSignature slot ring, submission queue
    and compiled-bucket cache.  Regions share the owning executor's pool,
    launch policy and config; everything shape- or body-specific lives here.
    """

    __slots__ = ("signature", "batched_fn", "ring", "queue", "compiled",
                 "stats", "buckets", "chunk",
                 "chunk_tuned", "queued_tasks", "waves", "tuned",
                 "_wave_peak", "_aot_parents", "cost", "_retuned_waves",
                 "_retuned_peak", "_donate", "quarantine", "bad_buckets",
                 "_wave_submitted", "warmup_wave", "breaker_state",
                 "_breaker_counts", "_breaker_wave_mark", "_breaker_mark",
                 "_breaker_open_waves")

    def __init__(self, signature: TaskSignature, batched_fn: Callable,
                 donate: bool, buckets: Tuple[int, ...] = (1,),
                 chunk: int = 0, quarantine_threshold: int = 2):
        self.signature = signature
        self.batched_fn = batched_fn
        self._donate = donate
        self.ring: Optional[SlotRing] = None
        self.queue: List[_Pending] = []
        self.queued_tasks = 0         # tasks queued (entries carry counts)
        self.compiled: Dict[Tuple, Callable] = {}
        self.buckets = buckets        # per-region ladder (auto-tune target)
        self.chunk = chunk            # mega-bucket inner chunk (0 = flat)
        self.chunk_tuned = False      # "auto" tuning ran for this region
        self.waves = 0                # completed waves (queue drained to 0)
        self.tuned = False
        self._wave_peak = 0
        self._aot_parents: Dict[Tuple, Tuple] = {}  # pk -> parent structs
        self.cost = BucketCostModel()     # measured bucket wall times (§10)
        self._retuned_waves = -1      # waves counter at the last retune
        self._retuned_peak = 0        # largest wave peak seen at last retune
        # blast-radius containment state (DESIGN.md §11)
        self.quarantine = QuarantineList(threshold=quarantine_threshold)
        self.bad_buckets: set = set()     # rungs banned by degraded mode
        self._wave_submitted = 0      # wave-relative task ids, reset per wave
        self.warmup_wave = 0          # wave size warmup was told about (§12)
        # per-family circuit breaker (DESIGN.md §14): sliding window of
        # per-wave fault counts; open = drain at the bucket-1 floor
        self.breaker_state = "closed"     # closed | open | half_open
        self._breaker_counts: List[int] = []
        self._breaker_wave_mark = 0   # waves counter at last breaker tick
        self._breaker_mark = 0        # cumulative faults at last tick
        self._breaker_open_waves = 0  # waves spent open (cooldown counter)
        self.stats = {"submitted": 0, "launches": 0, "aggregated_hist": {},
                      "queue_hist": {}, "ladder": list(buckets),
                      # warm-start observability (DESIGN.md §13): launches
                      # spent on stopwatch measurement, and cost-model
                      # predictions answered from the analytical prior
                      "measurement_launches": 0, "prior_hits": 0,
                      "breaker": "closed",
                      "faults": {"trips": 0, "bisection_launches": 0,
                                 "failed_tasks": 0, "quarantined": [],
                                 "retries": 0, "compile_failures": 0,
                                 "launch_failures": 0, "timeouts": 0,
                                 "breaker_trips": 0,
                                 "degraded_launches": 0}}

    # -- bucketed programs -------------------------------------------------
    def _eval(self, *stacked):
        """The body over a staged bucket, chunk-aware (DESIGN.md §9)."""
        return _chunked_eval(self.batched_fn, self.chunk, *stacked)

    def _apply_host(self, *stacked):
        return self._eval(*stacked)

    def _apply_gathered(self, idx, *parents):
        """Index-batched staging: one gather feeds the aggregation body."""
        return self._eval(*(jnp.take(p, idx, axis=0) for p in parents))

    def _apply_ring_prefix(self, bucket: int, start, *rings):
        """Ring staging: the bucket reads a zero-copy view of the filled
        prefix [start, start+bucket) straight out of the slot ring."""
        sliced = tuple(jax.lax.dynamic_slice_in_dim(r, start, bucket, axis=0)
                       for r in rings)
        return self._eval(*sliced)

    # -- compilation cache -------------------------------------------------
    # Each bucket size is a genuinely distinct XLA program (static shapes),
    # cached under ("ring"|"prefix"|"gather"|"host", bucket) — plus
    # parent-shape-keyed AOT entries ("gather"|"prefix_aot", bucket,
    # parent_shapes) installed by ``AggregationExecutor.warmup(
    # parent_shapes=...)``.
    def program(self, bucket: int, mode: str) -> Callable:
        """A new jitted program for one bucket and staging mode, named
        ``<kernel>_b<bucket>`` (``jit_<kernel>_b<bucket>`` on the device)
        whatever the mode, so a trace finds each family's device time."""
        name = program_name(self.signature.kernel, bucket)
        if mode in ("ring", "prefix"):
            return jax.jit(named(partial(self._apply_ring_prefix, bucket),
                                 name))
        if mode == "gather":
            return jax.jit(named(self._apply_gathered, name))
        return jax.jit(named(self._apply_host, name),
                       donate_argnums=(0,) if self._donate else ())

    def compiled_for(self, bucket: int, mode: str = "ring") -> Callable:
        key = (mode, bucket)
        fn = self.compiled.get(key)
        if fn is None:
            fn = self.compiled[key] = self.program(bucket, mode)
        return fn

    def ensure_ring(self, capacity: int,
                    example_args: Sequence[Any]) -> SlotRing:
        if self.ring is None:
            self.ring = SlotRing(capacity, example_args)
        return self.ring

    def expected_peak(self) -> int:
        """The modal observed wave peak (ties to the larger) — what the
        adaptive flush policies treat as 'a full wave'; 0 before any wave
        has completed (policies then behave eagerly)."""
        qh = self.stats["queue_hist"]
        if not qh:
            return 0
        return max(qh, key=lambda k: (qh[k], k))

    # -- AOT lowering (ONE recipe shared by warmup and ladder retune, so
    # the cache keys the _launch lookup probes are spelled out once) ------
    def aot_ref(self, bucket: int, parents: Sequence[Any]) -> None:
        """Pre-compile the indexed-gather + contiguous-prefix programs for
        one bucket over one parent set (ShapeDtypeStructs)."""
        pk = tuple(tuple(p.shape) for p in parents)
        if ("gather", bucket, pk) not in self.compiled:
            idx = jax.ShapeDtypeStruct((bucket,), jnp.int32)
            self.compiled[("gather", bucket, pk)] = self.program(
                bucket, "gather").lower(idx, *parents).compile()
        if ("prefix_aot", bucket, pk) not in self.compiled:
            start = jax.ShapeDtypeStruct((), jnp.int32)
            self.compiled[("prefix_aot", bucket, pk)] = self.program(
                bucket, "prefix").lower(start, *parents).compile()

    def aot_ring(self, bucket: int, ring_specs: Sequence[Any]) -> None:
        """Pre-compile the slot-ring prefix program for one bucket."""
        if ("ring", bucket) not in self.compiled:
            start = jax.ShapeDtypeStruct((), jnp.int32)
            self.compiled[("ring", bucket)] = self.program(
                bucket, "ring").lower(start, *ring_specs).compile()

    def reset_compiled(self) -> None:
        """Drop every compiled program and jit wrapper.  Needed when the
        inner chunk changes after compilation (a retune-time re-sweep):
        every cached trace baked the old chunk."""
        self.compiled.clear()


class AggregationExecutor:
    """Aggregates submissions of *kernel families* into bucketed launches.

    A registry of aggregation regions keyed by :class:`TaskSignature` lets
    tasks of different kernels AND different shapes coexist: each family
    gets its own slot ring, queue and compiled buckets, while the launch
    policy, executor pool and statistics are shared.  ``flush`` drains the
    live regions round-robin, so families interleave on the device instead
    of serializing.

    Parameters
    ----------
    batched_fn : callable, optional
        ``batched_fn(*stacked_args) -> stacked_out`` where every arg/out has
        a leading slot axis.  Registered as the default kernel family under
        ``name``; further families via :meth:`register`.  The body is one
        traced function shared by all its aggregated tasks (SGMT by
        construction), and serves every task shape submitted to it (each
        distinct shape opens its own region over the same body).
    config : AggregationConfig
        ``max_aggregated`` caps the bucket size (the paper's second launch
        criterion); ``n_executors`` sizes the underlying executor pool
        (combining strategy 3 with strategy 2, as the paper's best rows do);
        ``staging`` selects device-resident (slot ring / indexed gather) or
        the seed's host staging.
    """

    def __init__(self, batched_fn: Optional[Callable] = None,
                 config: Optional[AggregationConfig] = None,
                 pool: Optional[ExecutorPool] = None,
                 buffer_pool: Optional[BufferPool] = None,
                 donate: bool = False,
                 name: str = "region",
                 fault_injector: Optional[FaultInjector] = None):
        self.name = name
        self.config = config or AggregationConfig()
        self.pool = pool or ExecutorPool(self.config.n_executors)
        self.buffers = buffer_pool or DEFAULT_POOL
        self._buckets = tuple(sorted(self.config.bucket_sizes()))
        self._donate = donate
        ic = getattr(self.config, "inner_chunk", 0)
        self._chunk = int(ic) if ic != "auto" else 0   # "auto": set at warmup
        self._chunk_auto = ic == "auto"
        self._staging = getattr(self.config, "staging", "device")
        if self._staging not in ("device", "host"):
            raise ValueError(f"unknown staging mode {self._staging!r}")
        self._flush_policy = getattr(self.config, "flush_policy", "eager")
        fp_values = (self._flush_policy.values()
                     if isinstance(self._flush_policy, Mapping)
                     else (self._flush_policy,))
        for fp in fp_values:
            if fp not in ("eager", "watermark", "cost"):
                raise ValueError(
                    f"unknown flush_policy {fp!r} — valid "
                    f"policies: eager, watermark, cost")
        self._cost_on = bool(getattr(self.config, "cost_model", False))
        self._cost_samples = max(1, int(getattr(self.config,
                                                "cost_samples", 3)))
        # warm-start subsystem (DESIGN.md §13): the persistent tune store
        # (None -> cold start unless REPRO_TUNE_STORE points somewhere)
        # and the analytical prior for first-contact ladder derivation
        self._store = TuneStore.open(getattr(self.config, "tune_store",
                                             None))
        prior_mode = getattr(self.config, "prior", "off")
        if prior_mode not in ("off", "roofline"):
            raise ValueError(f"unknown prior mode {prior_mode!r} — valid "
                             f"modes: off, roofline")
        self._prior: Optional[RooflinePrior] = None
        self._prior_on = prior_mode == "roofline"
        if self._store is not None:
            enable_compile_cache()
        # blast-radius containment (DESIGN.md §11)
        self._guard = getattr(self.config, "guard", "off")
        if self._guard not in ("off", "finite"):
            raise ValueError(f"unknown guard mode {self._guard!r} — valid "
                             f"modes: off, finite")
        self._injector = fault_injector
        self._max_retries = max(0, int(getattr(self.config,
                                               "max_bucket_retries", 2)))
        self._retry_backoff = float(getattr(self.config,
                                            "retry_backoff_s", 0.0))
        self._retry_backoff_max = max(0.0, float(getattr(
            self.config, "retry_backoff_max_s", 1.0)))
        self._qthreshold = max(1, int(getattr(self.config,
                                              "quarantine_threshold", 2)))
        # launch watchdog + per-family circuit breaker (DESIGN.md §14)
        self._launch_timeout = max(0.0, float(getattr(
            self.config, "launch_timeout_s", 0.0)))
        self._breaker_window = max(0, int(getattr(
            self.config, "breaker_window", 0)))
        self._breaker_threshold = max(1, int(getattr(
            self.config, "breaker_threshold", 3)))
        self._breaker_cooldown = max(1, int(getattr(
            self.config, "breaker_cooldown", 2)))
        # (deadline, async out, region, bucket): launches the watchdog
        # must see complete before their wall-clock budget runs out
        self._watchdog_records: List[Tuple[float, Any, _Region, int]] = []
        self._guard_records: List[_LaunchRecord] = []
        self._bodies: Dict[str, Callable] = {}
        self._regions: Dict[TaskSignature, _Region] = {}
        self._default_kernel: Optional[str] = None
        # per-kernel routing cache for SlotView waves: kernel -> (parents,
        # sig).  A wave's submissions share one parent set per family, so
        # identity-comparing the parents skips the per-task signature
        # rebuild on the hot path — keyed per kernel so interleaved
        # multi-family waves (e.g. hydro + gravity) don't thrash it.
        self._sig_cache: Dict[str, Tuple[Tuple[Any, ...], TaskSignature]] = {}
        # statistics for the benchmark tables; per-family bucket histograms
        # live under "regions" (the multi-signature observability surface)
        self.stats = {"submitted": 0, "launches": 0, "aggregated_hist": {},
                      "staging_s": 0.0, "regions": {},
                      "warm_start": False,   # any region restored from store
                      "flush_policy": (dict(self._flush_policy)
                                       if isinstance(self._flush_policy,
                                                     Mapping)
                                       else self._flush_policy)}
        if batched_fn is not None:
            self.register(name, batched_fn)

    # -- region registry ---------------------------------------------------
    def register(self, kernel: str, batched_fn: Callable,
                 default: bool = False) -> str:
        """Register a kernel family's batched body.  The first registration
        (or ``default=True``) becomes the default for untagged submissions.
        Regions themselves are opened lazily, one per task signature."""
        if kernel in self._bodies and self._bodies[kernel] is not batched_fn:
            raise ValueError(
                f"kernel {kernel!r} already registered with a different body")
        self._bodies[kernel] = batched_fn
        if default or self._default_kernel is None:
            self._default_kernel = kernel
        return kernel

    def set_fault_injector(self,
                           injector: Optional[FaultInjector]) -> None:
        """Attach (or detach, with None) a deterministic fault schedule.
        Injection sites fire on the paths they model — payload faults on
        launch outputs, ring corruption at submission, compile/launch
        faults at dispatch — so containment is exercised end to end."""
        self._injector = injector

    def _region_for(self, kernel: str, args: Sequence[Any]) -> _Region:
        sig = TaskSignature.from_args(kernel, args)
        region = self._regions.get(sig)
        if region is None:
            body = self._bodies.get(kernel)
            if body is None:
                raise KeyError(f"no batched body registered for kernel "
                               f"{kernel!r} (have {sorted(self._bodies)})")
            region = _Region(sig, body, self._donate, buckets=self._buckets,
                             chunk=self._chunk,
                             quarantine_threshold=self._qthreshold)
            self._regions[sig] = region
            self.stats["regions"][sig.describe()] = region.stats
        return region

    def _region_for_views(self, kernel: str,
                          views: Sequence[SlotView]) -> _Region:
        """Region routing for all-SlotView submissions, cached on the
        parent-set identity (strong refs keep ids valid)."""
        parents = tuple(v.parent for v in views)
        c = self._sig_cache.get(kernel)
        if (c is not None and len(c[0]) == len(parents)
                and all(a is b for a, b in zip(c[0], parents))):
            region = self._regions.get(c[1])
            if region is not None:
                return region
        region = self._region_for(kernel, views)
        self._sig_cache[kernel] = (parents, region.signature)
        return region

    def _resolve_kernel(self, kernel: Optional[str]) -> str:
        kernel = kernel or self._default_kernel
        if kernel is None:
            raise RuntimeError("no kernel family registered — pass "
                               "batched_fn to the constructor or register()")
        return kernel

    @property
    def regions(self) -> Dict[TaskSignature, "_Region"]:
        """Live region registry (read-only view)."""
        return dict(self._regions)

    # -- single-region compatibility views --------------------------------
    def _sole_region(self) -> Optional[_Region]:
        if len(self._regions) == 1:
            return next(iter(self._regions.values()))
        return None

    @property
    def ring(self) -> Optional[SlotRing]:
        region = self._sole_region()
        return region.ring if region is not None else None

    @property
    def _queue(self) -> List[_Pending]:
        out: List[_Pending] = []
        for region in self._regions.values():
            out.extend(region.queue)
        return out

    @property
    def _compiled(self) -> Mapping[Tuple, Callable]:
        """Read-only view of the compiled-program caches (merged across
        regions); write through ``region.compiled`` instead — a write to
        this view would silently vanish in the multi-region case."""
        region = self._sole_region()
        if region is not None:
            return MappingProxyType(region.compiled)
        merged: Dict[Tuple, Callable] = {}
        for region in self._regions.values():
            merged.update(region.compiled)
        return MappingProxyType(merged)

    # -- warmup ------------------------------------------------------------
    def warmup(self, example_args: Optional[Tuple[Any, ...]] = None, *,
               kernel: Optional[str] = None,
               parent_shapes: Optional[Sequence[Any]] = None,
               buckets: Optional[Sequence[int]] = None,
               store: Optional[Any] = None) -> None:
        """AOT pre-compile every bucket size (amortized startup, like stream
        pre-allocation in CPPuddle).

        Buckets are lowered with ``.lower().compile()`` — no example
        execution, no broadcast staging, and no tracer hit on the first
        real submission.  Two modes, combinable:

        * ``example_args`` — per-task example inputs; pre-compiles the slot
          ring (device staging) or host-stacked (host staging) buckets.
        * ``parent_shapes`` — shapes/dtypes of the parent arrays that
          ``submit_indexed``/``submit_range`` will reference (arrays or
          ShapeDtypeStructs); pre-compiles the indexed-gather AND
          contiguous-prefix programs those submissions hit, closing the
          gather-mode warmup gap (DESIGN.md §6 -> §7).

        ``buckets`` restricts which ladder buckets are AOT-compiled (e.g.
        just the steady wave's greedy decomposition — the caller's compile
        budget); default is the region's whole ladder.  Un-warmed buckets
        still compile lazily on first use.

        ``store`` (DESIGN.md §13) points this warmup at a persistent
        :class:`TuneStore` (path or instance), overriding the config's
        ``tune_store`` knob: regions with a valid stored entry LOAD their
        tuned state (ladder, chunk, cost tables, strategy selection)
        instead of measuring it — zero measurement launches — and bucket
        compiles become persistent-cache disk hits.
        """
        kernel = self._resolve_kernel(kernel)
        if store is not None:
            self._store = TuneStore.open(store)
            if self._store is not None:
                enable_compile_cache()

        def aot_buckets(region):
            want = region.buckets if buckets is None else tuple(buckets)
            if region.stats.get("tuned_by") in ("store", "prior"):
                # a restored/seeded ladder is what the drain will use —
                # AOT ITS decomposition of the warmup wave too, or the
                # warm process pays lazy compiles the cold one never did
                # (callers pass ``buckets`` derived from the config
                # ladder, which the installed ladder supersedes)
                wave = region.warmup_wave
                if wave:
                    want = tuple(sorted(set(want).union(
                        greedy_decomposition(wave, region.buckets))))
            return want

        if parent_shapes is not None:
            parents = tuple(jax.ShapeDtypeStruct(tuple(p.shape), p.dtype)
                            for p in parent_shapes)
            task_specs = tuple(jax.ShapeDtypeStruct(p.shape[1:], p.dtype)
                               for p in parents)
            region = self._region_for(kernel, task_specs)
            pk = tuple(tuple(p.shape) for p in parents)
            region._aot_parents[pk] = parents    # retune re-AOTs from these
            restored = self._restore_region(region)
            if self._chunk_auto and not region.chunk_tuned:
                self._tune_chunk(region, parents)
            n_parent = min(p.shape[0] for p in parents)
            region.warmup_wave = max(region.warmup_wave, n_parent)
            if (self._prior_on and not restored
                    and not region.cost.measured()
                    and not region.cost.seeded()):
                self._seed_prior(region, parents)
            for b in (b for b in aot_buckets(region) if b <= n_parent):
                region.aot_ref(b, parents)
            if self._cost_on and not region.cost.seeded():
                self._measure_region(region, aot_buckets(region),
                                     parents=parents)
            if example_args is None:
                return
        if example_args is None:
            raise ValueError("warmup needs example_args and/or parent_shapes")
        region = self._region_for(kernel, example_args)
        restored = self._restore_region(region)
        specs = [jax.ShapeDtypeStruct(tuple(np.shape(a)),
                                      getattr(a, "dtype", None)
                                      or jnp.asarray(a).dtype)
                 for a in example_args]
        if self._chunk_auto and not region.chunk_tuned:
            # ring/host-staged regions tune too: a pseudo-parent of the
            # largest bucket's stacked shape drives the same measurement
            pseudo = tuple(jax.ShapeDtypeStruct(
                (max(region.buckets),) + s.shape, s.dtype) for s in specs)
            self._tune_chunk(region, pseudo)
        if (self._prior_on and not restored and not region.cost.measured()
                and not region.cost.seeded()):
            # ring-staged regions seed against the ring capacity: the
            # wave size is unknown before traffic, the cap bounds it
            pseudo = tuple(jax.ShapeDtypeStruct(
                (self.config.max_aggregated,) + s.shape, s.dtype)
                for s in specs)
            self._seed_prior(region, pseudo)
        if self._staging == "device":
            ring = region.ensure_ring(self.config.max_aggregated,
                                      example_args)
            ring_specs = [jax.ShapeDtypeStruct(r.shape, r.dtype)
                          for r in ring.buffers()]
            for b in aot_buckets(region):
                region.aot_ring(b, ring_specs)
            if self._cost_on and not region.cost.seeded():
                self._measure_region(region, aot_buckets(region),
                                     ring_specs=ring_specs)
        else:
            for b in aot_buckets(region):
                stacked = tuple(
                    jax.ShapeDtypeStruct((b,) + s.shape, s.dtype)
                    for s in specs)
                region.compiled[("host", b)] = region.program(
                    b, "host").lower(*stacked).compile()

    # -- persistent warm start (DESIGN.md §13) -----------------------------
    def _restore_region(self, region: _Region) -> bool:
        """Install the tune store's entry for this region, if one exists
        for this exact ``(backend, device_kind)`` + signature + code
        version: ladder (re-validated — a store is data, not trusted
        code), inner chunk, every cost-model path's table (tagged
        ``source="store"``, so ``_measure_region`` skips those buckets
        and ``_measure_alt_paths`` skips its probes), the observed queue
        histogram and the strategy selection.  The region comes up
        ``tuned``; autotune re-arms only on evidence beyond the stored
        histogram, exactly as after a live retune.  Any malformed field
        warns and leaves the region cold — a broken store must never
        crash (or mis-tune) the process it was meant to speed up."""
        if region.stats.get("tuned_by") == "store":
            return True                            # idempotent re-warmup
        if self._store is None:
            return False
        entry = self._store.get(_backend_key(), region.signature.describe())
        if not entry:
            return False
        try:
            ladder = validate_ladder([int(b) for b in entry["ladder"]],
                                     self.config.max_aggregated)
            cost_tables = {
                str(path): {int(b): float(t) for b, t in dict(table).items()}
                for path, table in dict(entry.get("cost_model",
                                                  {})).items()}
            queue_hist = {
                int(k): int(v)
                for k, v in dict(entry.get("queue_hist", {})).items()}
            chunk = entry.get("inner_chunk")
            chunk = None if chunk is None else int(chunk)
        except (KeyError, TypeError, ValueError) as err:
            warnings.warn(
                f"tune store entry for {region.signature.describe()} is "
                f"unusable ({err}) — falling back to cold-start "
                f"measurement", TuneStoreWarning, stacklevel=2)
            return False
        if chunk is not None:
            region.chunk = chunk
            region.chunk_tuned = True
            region.stats["inner_chunk"] = chunk
        for path, table in cost_tables.items():
            for b, sec in sorted(table.items()):
                region.cost.record(b, sec, path=path, source="store")
        region.buckets = ladder
        region.stats["ladder"] = list(ladder)
        qh = region.stats["queue_hist"]
        for k, c in queue_hist.items():
            qh[k] = qh.get(k, 0) + c
        region.warmup_wave = max(region.warmup_wave,
                                 int(entry.get("warmup_wave", 0) or 0))
        region.tuned = True
        region._retuned_waves = region.waves
        region._retuned_peak = max(queue_hist, default=0)
        for k in ("selected_strategy", "strategy_costs"):
            if k in entry:
                region.stats[k] = entry[k]
        if region.cost.measured():
            region.stats["cost_model"] = region.cost.as_stats()
        if len(region.cost.paths()) > 1:
            region.stats["cost_model_paths"] = region.cost.as_stats_paths()
        region.stats["tuned_by"] = "store"
        region.stats["cost_sources"] = {
            p: {b: s for b, s in t.items()}
            for p, t in region.cost.sources().items()}
        region.stats["warm_start"] = True
        self.stats["warm_start"] = True
        return True

    def _seed_prior(self, region: _Region,
                    parents: Sequence[Any]) -> None:
        """First contact without a stopwatch (DESIGN.md §13): fill the
        region's cost model with roofline estimates — every
        drain-reachable candidate bucket on "s3", the probe widths on
        "s2", the whole wave on "fused" — then derive a ladder from the
        analytical table.  Entries are tagged ``source="prior"`` and the
        region stays un-``tuned``: the normal autotune path re-derives
        from real measurements as waves arrive and retires the seeds."""
        wave = min(p.shape[0] for p in parents)
        if not wave:
            return
        if self._prior is None:
            self._prior = RooflinePrior(_backend_key())
        task_specs = tuple(jax.ShapeDtypeStruct(tuple(p.shape[1:]), p.dtype)
                           for p in parents)
        cap = self.config.max_aggregated
        for b in sorted(ladder_candidates({wave: 1}, cap)):
            region.cost.seed_prior(
                b, self._prior.predict(region.batched_fn, task_specs, b))
        for w in s2_width_candidates(wave):
            region.cost.seed_prior(
                w, self._prior.predict(region.batched_fn, task_specs, w),
                path="s2")
        region.cost.seed_prior(
            wave, self._prior.predict(region.batched_fn, task_specs, wave),
            path="fused")
        ladder = validate_ladder(
            derive_ladder({wave: 1}, cap, self.config.compile_budget,
                          region.cost), cap)
        region.buckets = ladder
        region.stats["ladder"] = list(ladder)
        region.stats["tuned_by"] = "prior"
        region.stats["cost_sources"] = {
            p: dict(t) for p, t in region.cost.sources().items()}
        region.stats["prior_hits"] = region.cost.prior_hits

    def _persist_region(self, region: _Region,
                        store: Optional[TuneStore] = None) -> None:
        """Write one region's tuned state into the store (measured
        medians only — priors are seeds, not knowledge worth saving)."""
        store = store or self._store
        entry: Dict[str, Any] = {
            "cost_model": {path: {str(b): region.cost.time(b, path)
                                  for b in region.cost.buckets(path)}
                           for path in region.cost.paths()},
            "ladder": [int(b) for b in region.buckets],
            "inner_chunk": int(region.chunk),
            "queue_hist": {str(k): int(v)
                           for k, v in region.stats["queue_hist"].items()},
            "warmup_wave": int(region.warmup_wave),
            "tuned_by": region.stats.get("tuned_by", "measured"),
        }
        for k in ("selected_strategy", "strategy_costs"):
            if k in region.stats:
                entry[k] = region.stats[k]
        store.put(_backend_key(), region.signature.describe(), entry)

    def save_tuning(self, store: Optional[Any] = None) -> Optional[str]:
        """Persist every tuned/measured region into the tune store (the
        executor's own, or an explicit ``store`` path/instance) and
        atomically write it to disk.  Returns the store file path, or
        None when there is no store to write to.  The write-back half of
        the §13 contract: ``warmup(store=...)`` loads, this saves."""
        target = TuneStore.open(store) if store is not None else self._store
        if target is None:
            return None
        wrote = False
        for region in self._regions.values():
            if region.tuned or region.cost.measured():
                self._persist_region(region, target)
                wrote = True
        if wrote or len(target) == 0:
            target.save()
        return target.path

    def _tune_chunk(self, region: _Region, parents: Sequence[Any],
                    force: bool = False) -> None:
        """``inner_chunk="auto"``: pick the region's mega-bucket chunk by
        timing the body on its largest bucket over candidate chunk sizes
        (0 = flat, then powers of two).  Runs once per region at warmup,
        before any bucket program is compiled, so every compiled program
        sees the chosen chunk; under ``cost_model=True`` the retune pass
        re-runs it with ``force=True`` (DESIGN.md §10 — the sweep follows
        the ladder to whatever bucket the tuner actually converged on,
        superseding the §9 warmup-only choice).  This is a measurement,
        not a lowering — tuning executes a handful of zero-filled buckets.
        Results are memoized per (backend+device kind, body, bucket
        shape), so re-tuning the same family in another executor (a
        benchmark sweep) is free, while a choice timed on one backend can
        never leak into another; ``force`` bypasses the memo read and
        overwrites the entry."""
        n_parent = min(p.shape[0] for p in parents)
        b = max((x for x in region.buckets if x <= n_parent), default=0)
        if b < 2:
            return
        key = (_backend_key(), id(region.batched_fn), b,
               tuple((tuple(p.shape[1:]), str(p.dtype)) for p in parents))
        memo = _CHUNK_TUNE_MEMO.get(key)
        if memo is not None and not force:
            region.chunk = memo[1]
            region.chunk_tuned = True
            region.stats["inner_chunk"] = memo[1]
            return
        stacked = tuple(jnp.zeros((b,) + tuple(p.shape[1:]), p.dtype)
                        for p in parents)
        best_chunk, best_t = 0, float("inf")
        for c in (0, 2, 4, 8):
            if c >= b or (c and b % c):
                continue
            fn = jax.jit(partial(_chunked_eval, region.batched_fn, c))
            try:
                jax.block_until_ready(fn(*stacked))    # compile + warm
            except (TypeError, ValueError):
                continue                               # body rejects chunking
            except Exception as err:
                # anything else (OOM, lowering bug, device loss) is NOT a
                # "this body dislikes chunking" signal — surface it with
                # the region/bucket context instead of silently pinning
                # chunk=0 (satellite of DESIGN.md §11)
                raise RegionFaultError(
                    f"inner-chunk tuning failed for region "
                    f"{region.signature.describe()} (bucket {b}, chunk "
                    f"{c}): {err}") from err
            # min-of-3 guards the choice against scheduler hiccups — the
            # memo pins it process-wide, so one noisy sample must not
            # lock in a pessimal chunk (~3.5x between best and worst here)
            region.stats["measurement_launches"] += 4   # warm + 3 timed
            t = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*stacked))
                t = min(t, time.perf_counter() - t0)
            if t < best_t:
                best_chunk, best_t = c, t
        # the memo holds a ref to the body so id() stays valid for the key
        while len(_CHUNK_TUNE_MEMO) >= _CHUNK_TUNE_MEMO_MAX:
            _CHUNK_TUNE_MEMO.pop(next(iter(_CHUNK_TUNE_MEMO)))
        _CHUNK_TUNE_MEMO[key] = (region.batched_fn, best_chunk)
        region.chunk = best_chunk
        region.chunk_tuned = True
        region.stats["inner_chunk"] = best_chunk

    # -- bucket cost measurement (DESIGN.md §10) ---------------------------
    def _measure_region(self, region: _Region, buckets: Sequence[int],
                        parents: Optional[Sequence[Any]] = None,
                        ring_specs: Optional[Sequence[Any]] = None) -> None:
        """Time each bucket's compiled program on zero-filled inputs into
        the region's :class:`BucketCostModel`: one warm call, then the
        median of ``cost_samples`` timed runs.  Ref-staged regions time
        the contiguous-prefix program (the steady bulk-submission fast
        path — gather-by-index costs the same body plus one take);
        ring-staged regions time the ring-prefix program.  Buckets that
        already have samples are skipped, so repeated warmups are free;
        a chunk re-sweep clears the model first (old timings described
        programs that no longer exist).  Host staging is never measured —
        it is the seed baseline, not a tuned hot path."""
        if parents is not None:
            concrete = tuple(jnp.zeros(tuple(p.shape), p.dtype)
                             for p in parents)

            def program(b):
                region.aot_ref(b, parents)
                pk = tuple(tuple(p.shape) for p in parents)
                return region.compiled[("prefix_aot", b, pk)]
        elif ring_specs is not None:
            concrete = tuple(jnp.zeros(tuple(r.shape), r.dtype)
                             for r in ring_specs)

            def program(b):
                region.aot_ring(b, ring_specs)
                return region.compiled[("ring", b)]
        else:
            return
        n_slots = min(c.shape[0] for c in concrete)
        start = jnp.int32(0)
        for b in sorted(set(buckets)):
            if b > n_slots or region.cost.time(b) is not None:
                continue
            fn = program(b)
            jax.block_until_ready(fn(start, *concrete))        # warm call
            region.stats["measurement_launches"] += 1 + self._cost_samples
            for _ in range(self._cost_samples):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(start, *concrete))
                region.cost.record(b, time.perf_counter() - t0)
        if parents is not None:
            self._measure_alt_paths(region, concrete)
        if region.cost.measured():
            region.stats["cost_model"] = region.cost.as_stats()
        if len(region.cost.paths()) > 1:
            region.stats["cost_model_paths"] = region.cost.as_stats_paths()

    def _measure_alt_paths(self, region: _Region,
                           concrete: Sequence[Any]) -> None:
        """Time the OTHER execution strategies' programs for this family
        (DESIGN.md §12), so ``select_strategy`` compares measured wall
        times instead of guessing: the s2 donated scatter per coalesce
        width, and the fused one-launch whole-wave body.  Measured once
        per region; the s2 widths probed are 1 plus powers of two up to
        the wave size.

        Families with an EXPLICIT route in ``family_strategies`` skip the
        probes whose result nothing would consult — each is a full XLA
        compile.  An explicit ``"s2"`` route still measures the s2 width
        table (the s2 strategy sizes its scatter ring from it); explicit
        ``"s3"`` / ``"fused"`` routes probe nothing here, and only
        ``"auto"`` (the default) measures every path for
        ``select_strategy`` to compare."""
        wave = min(c.shape[0] for c in concrete)
        if not wave:
            return
        if region.breaker_state != "closed":
            # breaker open/probing (§14): no alt-path probes — the family
            # is pinned to the s3 floor until the breaker closes
            return
        route = resolve_family_option(
            getattr(self.config, "family_strategies", None),
            region.signature.kernel, "auto")
        if route in ("auto", "s2") and not region.cost.measured("s2"):
            widths = measure_s2_widths(region.batched_fn, concrete,
                                       s2_width_candidates(wave),
                                       samples=self._cost_samples)
            region.stats["measurement_launches"] += (
                len(widths) * (1 + self._cost_samples))
            for w, t in widths.items():
                region.cost.record(w, t, path="s2")
        if route == "auto" and not region.cost.measured("fused"):
            fn = jax.jit(region.batched_fn)
            try:
                jax.block_until_ready(fn(*concrete))           # warm call
            except (TypeError, ValueError):
                return                    # body rejects the flat whole wave
            region.stats["measurement_launches"] += 1 + self._cost_samples
            for _ in range(self._cost_samples):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*concrete))
                region.cost.record(wave, time.perf_counter() - t0,
                                   path="fused")

    # -- per-family strategy selection (DESIGN.md §12) ---------------------
    def strategy_costs(self, kernel: str) -> Dict[str, Any]:
        """Predicted per-wave wall time (ms) of running ``kernel``'s wave
        under each measured execution strategy — the selection rationale
        persisted into the BENCH rows.  Empty before any measurement."""
        region = self._primary_region(kernel)
        if region is None:
            return {}
        wave = region.expected_peak() or region.warmup_wave
        if not wave:
            return {}
        out: Dict[str, Any] = {}
        if region.cost.has_data("s3"):
            ladder = [b for b in region.buckets
                      if b not in region.bad_buckets] or [1]
            out["s3"] = round(region.cost.predict_seq(
                greedy_decomposition(wave, ladder)) * 1e3, 4)
        s2 = region.cost.predict_s2_wave(wave)
        if s2 is not None:
            out["s2"] = round(s2[1] * 1e3, 4)
            out["s2_width"] = s2[0]
        if region.cost.has_data("fused"):
            out["fused"] = round(region.cost.predict(wave, "fused") * 1e3, 4)
        return out

    def select_strategy(self, kernel: str) -> str:
        """Pick the cheapest measured execution strategy for ``kernel``'s
        steady wave ("s2" | "s3" | "fused"; ties prefer "s3" — the
        aggregated path — then "s2").  Defaults to "s3" before any
        measurement.  The choice and its justification land in
        ``stats["regions"][fam]["selected_strategy"]`` /
        ``["strategy_costs"]``."""
        costs = self.strategy_costs(kernel)
        order = ("s3", "s2", "fused")
        timed = [(costs[s], order.index(s)) for s in order if s in costs]
        choice = min(timed)[1] if timed else 0
        selected = order[choice]
        region = self._primary_region(kernel)
        if region is not None:
            if region.breaker_state != "closed":
                # breaker not closed (§14): only the s3 path has the
                # bucket-1 floor + bisection machinery a faulting family
                # needs — cost comparison resumes once the breaker closes
                selected = "s3"
            region.stats["selected_strategy"] = selected
            if costs:
                region.stats["strategy_costs"] = costs
        return selected

    def record_selection(self, kernel: str, selected: str) -> None:
        """Persist an EXPLICIT per-family route (``family_strategies``)
        into the region stats, alongside whatever cost numbers exist —
        explicit and auto-selected assignments surface identically."""
        region = self._primary_region(kernel)
        if region is None:
            return
        region.stats["selected_strategy"] = selected
        costs = self.strategy_costs(kernel)
        if costs:
            region.stats["strategy_costs"] = costs

    def _primary_region(self, kernel: str) -> Optional[_Region]:
        """The region selection reasons about for a kernel: the one with
        the largest wave evidence (several regions per kernel can exist —
        one per task shape)."""
        regs = [r for s, r in self._regions.items() if s.kernel == kernel]
        if not regs:
            return None
        return max(regs, key=lambda r: (r.expected_peak() or r.warmup_wave))

    # -- submission API ----------------------------------------------------
    def submit(self, *args, kernel: Optional[str] = None) -> TaskFuture:
        """Queue one task, routed to its signature's region.  Args are
        either concrete per-task arrays (staged into the region's slot ring)
        or all :class:`SlotView` references (staged by a single gather at
        launch)."""
        kernel = self._resolve_kernel(kernel)
        fut = TaskFuture()
        is_ref = bool(args) and all(isinstance(a, SlotView) for a in args)
        if is_ref and self._staging == "device":
            region = self._region_for_views(kernel, args)
            if any(v.index != args[0].index for v in args[1:]):
                raise ValueError(
                    "SlotView args of one task must share one index — a "
                    "launch gathers the SAME slot from every parent "
                    "(use submit_indexed)")
            entry = _Pending(future=fut, views=tuple(args))
        elif self._staging == "host" or not args:
            region = self._region_for(kernel, args)
            args = tuple(a.parent[a.index] if isinstance(a, SlotView) else a
                         for a in args)
            entry = _Pending(future=fut, args=args)
        else:
            region = self._region_for(kernel, args)
            args = tuple(a.parent[a.index] if isinstance(a, SlotView) else a
                         for a in args)
            with span("repro.staging", self.stats, "staging_s",
                      kernel=kernel):
                ring = region.ensure_ring(self.config.max_aggregated, args)
                if ring.fill >= ring.capacity:
                    # watermark remainders left a partial prefix consumed;
                    # slide the live tail to the front (one fused device op)
                    first = (region.queue[0].slot if region.queue
                             else ring.fill)
                    ring.compact(first)
                    for p in region.queue:
                        p.slot -= first
                slot = ring.write(args)
                if self._injector is not None:
                    # ring-corruption site: this task's staged inputs go bad
                    # between submission and launch (bad DMA / stale buffer)
                    bad = self._injector.corrupt_ring(
                        kernel, region.waves, region._wave_submitted)
                    if bad is not None:
                        ring.poison(slot, bad)
                entry = _Pending(future=fut, slot=slot)
        self._enqueue(region, entry)
        return fut

    def submit_range(self, parents: Tuple[jax.Array, ...], start: int,
                     n: int, kernel: Optional[str] = None) -> RangeFuture:
        """Bulk submission: enqueue tasks ``start .. start+n-1`` of a parent
        set as ONE queue entry backed by ONE :class:`RangeFuture`.

        Replaces n ``submit_indexed`` calls (n ``TaskFuture`` allocations, n
        signature routings, n queue appends) with one of each — the
        submission loop stops being a per-task Python cost.  The range may
        still drain across several bucketed launches (greedy, in order);
        ``result()``/``gather_futures`` reassemble it, zero-copy in the
        steady one-launch case.  Launch criteria see all n tasks at once, so
        a full wave triggers its mega-bucket immediately on submission.
        """
        if n <= 0:
            raise ValueError(f"submit_range needs n >= 1, got {n}")
        if self._staging != "device":
            raise ValueError(
                "submit_range requires device staging — ranges reference "
                "device-resident parents by slot index (use per-task "
                "submit() under staging='host')")
        kernel = self._resolve_kernel(kernel)
        n_parent = min(p.shape[0] for p in parents)
        if start < 0 or start + n > n_parent:
            # XLA's dynamic_slice/take CLAMP out-of-bounds indices instead
            # of failing — an unchecked range would silently return data
            # from the wrong slots
            raise ValueError(
                f"range [{start}, {start + n}) out of bounds for parents "
                f"with {n_parent} slots")
        views = tuple(SlotView(p, start) for p in parents)
        region = self._region_for_views(kernel, views)
        fut = RangeFuture(n)
        entry = _Pending(future=fut, views=views, count=n)
        self._enqueue(region, entry)
        return fut

    def _enqueue(self, region: _Region, entry: _Pending) -> None:
        self._check_mode(region, entry)
        # wave-relative task identity (§11): position within the current
        # submission wave — stable across re-executions, and what payload
        # fault specs and the quarantine list key on
        entry.wave_index = region._wave_submitted
        region._wave_submitted += entry.count
        region.queue.append(entry)
        region.queued_tasks += entry.count
        region._wave_peak = max(region._wave_peak, region.queued_tasks)
        self.stats["submitted"] += entry.count
        region.stats["submitted"] += entry.count
        self._maybe_launch()

    def submit_indexed(self, parents: Tuple[jax.Array, ...], index: int,
                       kernel: Optional[str] = None) -> TaskFuture:
        """Sugar: submit task ``i`` whose j-th arg is ``parents[j][i]``."""
        return self.submit(*(SlotView(p, index) for p in parents),
                           kernel=kernel)

    def _check_mode(self, region: _Region, entry: _Pending) -> None:
        """A bucket must stage uniformly: same mode, and for ref entries the
        same parent arrays (a launch gathers from ONE parent set).  Launch
        the region's queue before admitting an incompatible entry."""
        if not region.queue:
            return
        head = region.queue[0]
        compatible = self._entry_mode(head) == self._entry_mode(entry)
        if compatible and entry.views is not None:
            compatible = all(a.parent is b.parent
                             for a, b in zip(head.views, entry.views))
        if not compatible:
            while region.queue:
                self._launch(region,
                             self._largest_bucket(region,
                                                  region.queued_tasks))

    @staticmethod
    def _entry_mode(entry: _Pending) -> str:
        if entry.views is not None:
            return "ref"
        if entry.args is not None:
            return "host"
        return "ring"

    def _maybe_launch(self) -> None:
        """The paper's launch policy, per region: launch when (a) the cap is
        reached, or (b) an underlying executor is idle AND the flush policy
        agrees that draining the partial queue now beats waiting for a
        fuller bucket; otherwise keep aggregating.  Regions progress
        independently — a full family never stalls behind another family's
        partial queue."""
        progress = True
        while progress:
            progress = False
            for region in self._regions.values():
                q = region.queued_tasks
                if q >= self.config.max_aggregated:
                    self._launch(region,
                                 self._largest_bucket(
                                     region, self.config.max_aggregated))
                    progress = True
                elif (q >= self.config.launch_watermark
                      and self.pool.any_idle()
                      and self._idle_drain_pays(region, q)):
                    self._launch(region, self._largest_bucket(region, q))
                    progress = True

    def _policy_for(self, region: _Region) -> str:
        """The region's flush policy: the config value, resolved per family
        when it is a mapping (exact kernel -> "+epi" base -> "*" -> eager,
        DESIGN.md §12)."""
        return resolve_family_option(self._flush_policy,
                                     region.signature.kernel, "eager")

    def _idle_drain_pays(self, region: _Region, q: int) -> bool:
        """The watermark-adaptive flush decision (DESIGN.md §10): should a
        partial queue of ``q`` tasks drain into an idle executor, or keep
        aggregating toward the region's typical wave?

        * ``eager`` — always drain (the §4 policy, and the fallback of the
          adaptive policies until a wave peak / cost model exists);
        * ``watermark`` — drain only at/after the *learned* wave peak, so
          partial buckets stop leaking once the steady wave size is known;
        * ``cost`` — drain early only when the measured model predicts the
          split drain (q now + the remainder later) to be no slower than
          waiting and draining the full wave in one greedy pass — i.e.
          exactly when the big bucket's measured cost is superlinear
          enough that splitting it is free.

        Non-eager consultations leave a decision trace in
        ``stats["regions"][fam]["flush_decisions"]`` (consulted /
        drained_early / held counters), so a policy's behaviour under a
        live watermark is observable in the BENCH rows.
        """
        policy = self._policy_for(region)
        if policy == "eager":
            return True
        trace = region.stats.setdefault(
            "flush_decisions", {"policy": policy, "consulted": 0,
                                "full_wave": 0, "drained_early": 0,
                                "held": 0})
        trace["consulted"] += 1
        peak = region.expected_peak()
        if not peak or q >= peak:
            trace["full_wave"] += 1
            return True               # no history yet, or a full wave: go
        if policy == "watermark":
            trace["held"] += 1
            return False
        if not region.cost.measured():
            trace["drained_early"] += 1
            return True               # "cost" without a model: eager
        split = (region.cost.predict_seq(
                     greedy_decomposition(q, region.buckets))
                 + region.cost.predict_seq(
                     greedy_decomposition(peak - q, region.buckets)))
        full = region.cost.predict_seq(
            greedy_decomposition(peak, region.buckets))
        pays = split <= full
        trace["drained_early" if pays else "held"] += 1
        return pays

    @staticmethod
    def _largest_bucket(region: _Region, k: int) -> int:
        if region.breaker_state == "open":
            # breaker open (§14): the family drains at the never-banned
            # bucket-1 floor until the cooldown readmits the ladder
            return 1
        best = region.buckets[0]
        for b in region.buckets:
            # degraded mode (§11): rungs banned after repeated compile/
            # launch failures are skipped; bucket 1 is never banned, so a
            # remainder bucket always survives
            if b <= k and b not in region.bad_buckets:
                best = b
        if best > k:
            raise RuntimeError(
                f"bucket {best} exceeds queue length {k} — ladder "
                f"{region.buckets} lacks a remainder bucket (validate_ladder "
                f"should have rejected it)")
        return best

    def _take(self, region: _Region, k: int) -> List[_Pending]:
        """Pop k tasks' worth of entries off the queue, splitting a range
        entry at the bucket boundary (both halves share the RangeFuture)."""
        taken: List[_Pending] = []
        need = k
        while need:
            e = region.queue[0]
            if e.count <= need:
                taken.append(region.queue.pop(0))
                need -= e.count
            else:
                head, tail = e.split(need)
                region.queue[0] = tail
                taken.append(head)
                need = 0
        region.queued_tasks -= k
        return taken

    def _launch(self, region: _Region, k: int) -> None:
        tasks = self._take(region, k)
        mode = self._entry_mode(tasks[0])
        self._launch_tasks(region, tasks, k, mode)
        if mode == "ring" and not region.queue:
            region.ring.swap()    # in-flight launch keeps the old buffer
        if not region.queue:
            self._wave_complete(region)

    def _stage(self, region: _Region, tasks: List[_Pending], k: int,
               mode: str):
        """One bucket's inputs -> (fn, call_args, parents, indices): the
        compiled program plus the §11 re-execution recipe — ``parents`` are
        the concrete arrays the region's gather program can re-run any
        position subset against (parent set / launched ring buffers /
        stacked host batch), ``indices`` each position's absolute index into
        them."""
        if mode == "ref":
            indices: List[int] = []
            for t in tasks:
                i0 = t.views[0].index
                indices.extend(range(i0, i0 + t.count))
            parents = tuple(v.parent for v in tasks[0].views)
            pk = tuple(tuple(p.shape) for p in parents)
            if pk not in region._aot_parents:    # remember for retune AOT
                region._aot_parents[pk] = tuple(
                    jax.ShapeDtypeStruct(tuple(p.shape), p.dtype)
                    for p in parents)
            if indices == list(range(indices[0], indices[0] + k)):
                # contiguous slot run: one dynamic slice of the parent (the
                # parent IS the ring) — no gather, no index array
                fn = (region.compiled.get(("prefix_aot", k, pk))
                      or region.compiled_for(k, "prefix"))
                call_args = (jnp.int32(indices[0]),) + parents
            else:
                idx = jnp.asarray(indices, jnp.int32)
                fn = (region.compiled.get(("gather", k, pk))
                      or region.compiled_for(k, "gather"))
                call_args = (idx,) + parents
        elif mode == "ring":
            first = tasks[0].slot
            parents = region.ring.buffers()   # concrete refs: a later swap
            indices = list(range(first, first + k))   # cannot invalidate
            fn = region.compiled_for(k, "ring")
            call_args = (jnp.int32(first),) + parents
        else:
            stacked = []
            for j in range(len(tasks[0].args)):
                parts = [t.args[j] for t in tasks]
                if k == 1:
                    stacked.append(jnp.asarray(parts[0])[None])
                elif isinstance(parts[0], jax.Array):
                    stacked.append(jnp.stack(parts))
                else:
                    stacked.append(jnp.asarray(self.buffers.stage(parts)))
            parents = tuple(stacked)
            indices = list(range(k))
            fn = region.compiled_for(k, "host")
            call_args = parents
        return fn, call_args, parents, indices

    def _launch_tasks(self, region: _Region, tasks: List[_Pending], k: int,
                      mode: str, degraded: bool = False) -> None:
        """Stage + dispatch one bucket of TAKEN tasks and fulfil their
        futures; under ``guard="finite"`` the launch is also recorded for
        the post-drain audit.  A compile/launch fault degrades the bucket
        (``_degrade``) instead of propagating — the wave survives."""
        with span("repro.staging", self.stats, "staging_s",
                  kernel=region.signature.kernel, bucket=k):
            fn, call_args, parents, indices = self._stage(region, tasks, k,
                                                          mode)
        try:
            out = self._dispatch(region, fn, call_args, k)
        except (BucketCompileError, LaunchFaultError,
                LaunchTimeoutError) as err:
            self._degrade(region, tasks, k, mode, err)
            return
        wave_ids: List[int] = []
        for t in tasks:
            wave_ids.extend(range(t.wave_index, t.wave_index + t.count))
        poisoned: Dict[int, str] = {}
        if self._injector is not None:
            # payload site: the matched tasks' outputs go non-finite (the
            # NaN blow-up / bad tenant input the guard exists to contain)
            hit = self._injector.poison_positions(
                region.signature.kernel, region.waves, wave_ids)
            if hit:
                out = poison_slots(out, sorted(hit), hit)
                poisoned = {wave_ids[p]: m for p, m in hit.items()}
        slot = 0
        for t in tasks:
            if isinstance(t.future, RangeFuture):
                t.future._fulfil_range(out, slot, t.fut_offset, t.count)
            else:
                t.future._fulfil(out, slot)
            slot += t.count
        if self._guard == "finite":
            # dispatch the finite reduction NOW (non-blocking) so it
            # overlaps the staging/dispatch of later launches in the
            # drain; _run_guard only forces the boolean post-drain
            self._guard_records.append(_LaunchRecord(
                region=region, out=out, k=k, parents=parents,
                indices=indices, tasks=list(tasks), wave_ids=wave_ids,
                wave=region.waves, poisoned=poisoned,
                verdict=all_finite_async(out)))
        self.stats["launches"] += 1
        hist = self.stats["aggregated_hist"]
        hist[k] = hist.get(k, 0) + 1
        region.stats["launches"] += 1
        rhist = region.stats["aggregated_hist"]
        rhist[k] = rhist.get(k, 0) + 1
        if degraded:
            region.stats["faults"]["degraded_launches"] += 1

    def _dispatch(self, region: _Region, fn: Callable, call_args, k: int):
        """One pool launch with the §11 dispatch-site injection and the
        bounded-retry policy: launch faults AND watchdog timeouts are
        transient by assumption (retried with exponential backoff from
        ``retry_backoff_s``, each sleep capped at ``retry_backoff_max_s``),
        compile faults deterministic (never retried — the same program
        cannot succeed on attempt two).  With ``launch_timeout_s`` set the
        dispatch stays non-blocking, but the async result is recorded for
        the flush-time watchdog (§14), which bounds its completion."""
        kern = region.signature.kernel
        faults = region.stats["faults"]
        attempts = 0
        while True:
            try:
                inj = self._injector
                if inj is not None:
                    if inj.compile_fails(kern, k):
                        faults["compile_failures"] += 1
                        raise BucketCompileError(
                            f"injected compile failure: kernel {kern!r} "
                            f"bucket {k}")
                    lf = inj.launch_fault(kern, k)
                    if lf is not None:
                        fmode, delay = lf
                        if fmode == "delay":
                            time.sleep(delay)
                        elif fmode == "hang":
                            # the launch would never complete: only a
                            # configured watchdog budget can end the stall
                            if not self._launch_timeout:
                                raise RegionFaultError(
                                    f"injected hang: kernel {kern!r} bucket "
                                    f"{k} would stall the drain forever "
                                    f"(no watchdog — set "
                                    f"AggregationConfig.launch_timeout_s)")
                            # model the consumed budget without actually
                            # parking tests for launch_timeout_s seconds
                            time.sleep(min(self._launch_timeout, 0.01))
                            faults["timeouts"] += 1
                            raise LaunchTimeoutError(
                                f"launch of kernel {kern!r} bucket {k} hung "
                                f"past launch_timeout_s="
                                f"{self._launch_timeout}")
                        else:
                            faults["launch_failures"] += 1
                            raise LaunchFaultError(
                                f"injected launch failure: kernel {kern!r} "
                                f"bucket {k}")
                out = self.pool.get().launch(fn, *call_args, family=kern,
                                             bucket=k)
                if self._launch_timeout:
                    self._watchdog_records.append(
                        (time.monotonic() + self._launch_timeout, out,
                         region, k))
                return out
            except BucketCompileError:
                raise
            except (LaunchFaultError, LaunchTimeoutError):
                if attempts >= self._max_retries:
                    raise
                attempts += 1
                faults["retries"] += 1
                if self._retry_backoff:
                    time.sleep(min(
                        self._retry_backoff * (2 ** (attempts - 1)),
                        self._retry_backoff_max))

    def _degrade(self, region: _Region, tasks: List[_Pending], k: int,
                 mode: str, err: Exception) -> None:
        """Graceful degradation (§11): ban the failing rung and re-drain
        the taken tasks greedily through the remaining good rungs — down
        to per-task bucket-1 launches, the degraded floor.  A failure AT
        bucket 1 has nowhere smaller to fall: those tasks fail, with the
        dispatch error attached to their futures."""
        if k == 1:
            self._fail_tasks(region, tasks, err)
            return
        region.bad_buckets.add(k)
        remaining = list(tasks)
        n_left = sum(t.count for t in remaining)
        while n_left:
            good = [b for b in region.buckets
                    if b <= n_left and b not in region.bad_buckets]
            b = max(good) if good else 1
            head, remaining = _split_taken(remaining, b)
            self._launch_tasks(region, head, b, mode, degraded=True)
            n_left -= b

    def _fail_tasks(self, region: _Region, tasks: List[_Pending],
                    err: Exception) -> None:
        n = 0
        for t in tasks:
            ids = tuple(range(t.wave_index, t.wave_index + t.count))
            cause = TaskFailedError(
                f"task(s) {list(ids)} of {region.signature.describe()} "
                f"failed: {err}", task_ids=ids,
                kernel=region.signature.kernel)
            cause.__cause__ = err
            if isinstance(t.future, RangeFuture):
                t.future._fail_range(t.fut_offset, t.count, cause)
            else:
                t.future._fail(cause)
            n += t.count
        region.stats["faults"]["failed_tasks"] += n

    # -- post-drain guard: detection, bisection, containment (§11) ---------
    def _run_guard(self) -> None:
        """ONE scalar all-finite check per drained launch (the guarded-
        but-untripped cost); a tripped launch's futures are retracted and
        re-resolved by ladder bisection."""
        records, self._guard_records = self._guard_records, []
        for rec in records:
            if bool(rec.verdict):
                continue
            self._contain(rec)

    def _contain(self, rec: _LaunchRecord) -> None:
        """Isolate the offending slot(s) of a tripped launch in O(log
        bucket) re-executions: quarantined repeat offenders short-circuit
        to per-task groups, everything else halves recursively; clean
        groups re-fulfil their futures bit-identically (batch
        decomposition is exact), non-finite singletons fail."""
        region = rec.region
        faults = region.stats["faults"]
        faults["trips"] += 1
        for t in rec.tasks:
            if isinstance(t.future, RangeFuture):
                t.future._retract(rec.out)
            else:
                t.future._retract()
        # position -> (owning entry, entry's first position)
        owner: Dict[int, Tuple[_Pending, int]] = {}
        pos = 0
        for t in rec.tasks:
            for p in range(pos, pos + t.count):
                owner[p] = (t, pos)
            pos += t.count
        quarantined = [p for p in range(rec.k)
                       if rec.wave_ids[p] in region.quarantine]
        rest = [p for p in range(rec.k)
                if rec.wave_ids[p] not in region.quarantine]
        # the root group is KNOWN bad only when no quarantined position
        # could be carrying the trip — then its own re-execution is skipped
        groups: List[Tuple[List[int], bool]] = [([p], False)
                                                for p in quarantined]
        if rest:
            groups.append((rest, not quarantined))
        culprits: List[int] = []
        while groups:
            grp, known_bad = groups.pop()
            if known_bad:
                if len(grp) == 1:
                    culprits.append(grp[0])
                else:
                    mid = len(grp) // 2
                    groups.append((grp[:mid], False))
                    groups.append((grp[mid:], False))
                continue
            out = self._reexec(rec, grp)
            faults["bisection_launches"] += 1
            if all_finite(out):
                self._refulfil(rec, owner, grp, out)
            elif len(grp) == 1:
                culprits.append(grp[0])
            else:
                mid = len(grp) // 2
                groups.append((grp[:mid], False))
                groups.append((grp[mid:], False))
        for p in culprits:
            tid = rec.wave_ids[p]
            region.quarantine.record_offense(tid)
            faults["quarantined"] = region.quarantine.as_stats()
            err = TaskFailedError(
                f"non-finite output isolated to task {tid} of "
                f"{region.signature.describe()} (wave {rec.wave}, launch "
                f"bucket {rec.k})", task_ids=(tid,),
                kernel=region.signature.kernel)
            t, first = owner[p]
            if isinstance(t.future, RangeFuture):
                t.future._fail_range(t.fut_offset + (p - first), 1, err)
            else:
                t.future._fail(err)
        faults["failed_tasks"] += len(culprits)

    def _reexec(self, rec: _LaunchRecord, grp: List[int]):
        """Re-execute one position subset through the region's shape-
        polymorphic gather program.  Injected payload poison is re-applied
        by wave id (the poison is a property of the TASK), so a poisoned
        task stays non-finite at every bucket size and bisection converges
        on it; survivors come back bit-identical to their unaggregated
        results — the no-padding equivalence invariant."""
        region = rec.region
        idx = jnp.asarray([rec.indices[p] for p in grp], jnp.int32)
        out = self.pool.get().launch(region.compiled_for(len(grp), "gather"),
                                     idx, *rec.parents,
                                     family=region.signature.kernel,
                                     bucket=len(grp))
        pois = {j: rec.poisoned[rec.wave_ids[p]]
                for j, p in enumerate(grp)
                if rec.wave_ids[p] in rec.poisoned}
        if pois:
            out = poison_slots(out, sorted(pois), pois)
        return out

    @staticmethod
    def _refulfil(rec: _LaunchRecord, owner: Dict[int, Tuple[_Pending, int]],
                  grp: List[int], out: Any) -> None:
        """Fulfil a clean re-executed group (bisection keeps groups as
        contiguous position runs, so segment assembly stays slice-shaped)."""
        for j, p in enumerate(grp):
            t, first = owner[p]
            if isinstance(t.future, RangeFuture):
                t.future._fulfil_range(out, j, t.fut_offset + (p - first), 1)
            else:
                t.future._fulfil(out, j)

    # -- launch watchdog + per-family circuit breaker (DESIGN.md §14) ------
    def _enforce_watchdog(self) -> None:
        """Bound completion of every recorded launch by its wall-clock
        deadline.  The wait must be EXACT, not polled: sleep-tick polling
        rounds every healthy in-flight wave up to the tick size (measured
        ~9% on the paired overhead row at 10 ms ticks), and busy-spinning
        steals cores from the very compute it waits on (measured ~77% on
        the CPU backend).  So a daemon thread performs the native blocking
        wait (``block_until_ready`` releases the GIL) and the caller waits
        on an event with the deadline as timeout — a healthy wave costs
        one thread spawn + a condition-variable wait, a genuinely hung
        buffer raises :class:`LaunchTimeoutError` within its budget while
        the abandoned waiter thread stays parked on the dead buffer."""
        records, self._watchdog_records = self._watchdog_records, []

        def _leaves(out):
            return [x for x in jax.tree_util.tree_leaves(out)
                    if hasattr(x, "is_ready")]

        pending = [x for _, out, _, _ in records for x in _leaves(out)
                   if not x.is_ready()]
        if not pending:
            return
        done = threading.Event()

        def _block():
            try:
                for x in pending:
                    try:
                        x.block_until_ready()
                    except Exception:
                        pass          # pool.drain() surfaces launch errors
            finally:
                done.set()

        threading.Thread(target=_block, daemon=True,
                         name="agg-watchdog").start()
        # one deadline per flush (the latest record's): each record's own
        # budget started at its dispatch, so the bound a true hang sees is
        # at most launch_timeout_s past the wave's last dispatch
        deadline = max(r[0] for r in records)
        if done.wait(max(0.0, deadline - time.monotonic())):
            return
        for _, out, region, k in records:      # blame the stuck launch
            if not all(x.is_ready() for x in _leaves(out)):
                region.stats["faults"]["timeouts"] += 1
                raise LaunchTimeoutError(
                    f"launch of kernel "
                    f"{region.signature.kernel!r} bucket {k} exceeded "
                    f"launch_timeout_s={self._launch_timeout}")
        # everything completed between the timeout and the scan: healthy

    def _update_breakers(self) -> None:
        """Advance every region's circuit breaker at flush time (after the
        guard audit, so a wave's trips are visible).  Faults are counted as
        a cumulative-counter delta (guard trips + launch failures +
        timeouts) over the waves completed since the last tick."""
        if not self._breaker_window:
            return
        for region in self._regions.values():
            elapsed = region.waves - region._breaker_wave_mark
            if not elapsed:
                continue
            region._breaker_wave_mark = region.waves
            f = region.stats["faults"]
            cum = f["trips"] + f["launch_failures"] + f["timeouts"]
            delta = cum - region._breaker_mark
            region._breaker_mark = cum
            if region.breaker_state == "closed":
                region._breaker_counts.extend(
                    [delta] + [0] * (elapsed - 1))
                del region._breaker_counts[:-self._breaker_window]
                if sum(region._breaker_counts) >= self._breaker_threshold:
                    self._trip_breaker(region)
            elif region.breaker_state == "open":
                region._breaker_open_waves += elapsed
                if region._breaker_open_waves >= self._breaker_cooldown:
                    # cooled down: the next wave runs the full ladder as a
                    # probe — clean closes the breaker, faulty re-opens it
                    region.breaker_state = "half_open"
            else:                                          # half_open probe
                if delta:
                    self._trip_breaker(region)
                else:
                    region.breaker_state = "closed"
                    region._breaker_counts = []
            region.stats["breaker"] = region.breaker_state

    def _trip_breaker(self, region: _Region) -> None:
        region.breaker_state = "open"
        region._breaker_open_waves = 0
        region._breaker_counts = []
        region.stats["breaker"] = "open"
        region.stats["faults"]["breaker_trips"] += 1

    def breaker_state(self, kernel: str) -> str:
        """The breaker state of ``kernel``'s primary region ("closed" |
        "open" | "half_open"); "closed" for unknown families.  The mixed
        router consults this per wave: a not-closed family is pinned to
        the s3 bucket-1 floor regardless of its cached route."""
        region = self._primary_region(kernel)
        return region.breaker_state if region is not None else "closed"

    def breaker_states(self) -> Dict[str, str]:
        """Per-family breaker states (families with several shape regions
        report their worst state: open > half_open > closed) — the
        ``ServingEngine.healthz()`` surface."""
        rank = {"closed": 0, "half_open": 1, "open": 2}
        out: Dict[str, str] = {}
        for sig, region in self._regions.items():
            prev = out.get(sig.kernel, "closed")
            if rank[region.breaker_state] >= rank[prev]:
                out[sig.kernel] = region.breaker_state
        return out

    # -- ladder auto-tuning ------------------------------------------------
    def _wave_complete(self, region: _Region) -> None:
        """A wave ended (queue drained to zero): record its peak queue
        length and, past the warmup, re-derive the region's ladder."""
        region._wave_submitted = 0    # wave-relative task ids restart
        region.stats["prior_hits"] = region.cost.prior_hits
        peak = region._wave_peak
        if peak:
            qh = region.stats["queue_hist"]
            qh[peak] = qh.get(peak, 0) + 1
            region.waves += 1
            region._wave_peak = 0
            if region.tuned and peak > region._retuned_peak:
                # the workload outgrew anything the last retune SAW (e.g.
                # warmup saw only watermark-drained micro-waves, then a
                # bulk range arrived): re-arm the tuner instead of pinning
                # the small ladder forever.  The trigger is new EVIDENCE
                # (a peak beyond the tuned histogram), never the ladder
                # shape — a measured tuner may legitimately pick a ladder
                # whose max bucket is below the wave (splitting predicted
                # faster), and comparing against max(buckets) would then
                # re-arm, and re-tune, on every single wave
                region.tuned = False
        if (self.config.autotune and not region.tuned
                and region.waves >= self.config.autotune_warmup):
            self._retune_region(region)

    def _retune_region(self, region: _Region) -> None:
        """Swap in the ladder minimizing the per-wave objective — expected
        launches, or predicted wall time under ``cost_model=True`` — and
        AOT-compile the new buckets for every parent set seen, as the AMR
        follow-up work does once launch overhead stops dominating.

        The measured path (DESIGN.md §10) runs three extra steps first:
        re-sweep ``inner_chunk="auto"`` against the current backend (a
        chunk change invalidates every compiled program AND every cost
        sample — both are rebuilt), then time every drain-reachable
        candidate bucket (:func:`ladder_candidates`), then hand the model
        to :func:`derive_ladder`.  Candidate measurement compiles more
        programs than ``compile_budget`` — the budget bounds the ladder
        the steady state keeps, not what the tuner is allowed to probe.
        """
        region._retuned_waves = region.waves
        region._retuned_peak = max(
            (k for k in region.stats["queue_hist"] if k > 0), default=0)
        chunk_changed = False
        cost_model = None
        if self._cost_on:
            chunk_changed = self._resweep_chunk(region)
            cost_model = self._measure_candidates(region)
        ladder = derive_ladder(region.stats["queue_hist"],
                               self.config.max_aggregated,
                               self.config.compile_budget, cost_model)
        region.tuned = True
        region.stats["tuned_by"] = ("measured" if cost_model is not None
                                    else "launches")
        if cost_model is not None:
            # real measurements just landed: retire the analytical seeds
            # (DESIGN.md §13 — priors are fully replaced by retune)
            region.cost.clear_priors()
            region.stats["cost_sources"] = {
                p: dict(t) for p, t in region.cost.sources().items()}
        region.stats["prior_hits"] = region.cost.prior_hits
        if ladder != region.buckets or chunk_changed:
            region.buckets = ladder
            region.stats["ladder"] = list(ladder)
            # AOT only the buckets the observed waves will actually drain
            # through under the new ladder (the compile budget, honored)
            used = set()
            for k in region.stats["queue_hist"]:
                used.update(greedy_decomposition(k, ladder))
            if region.ring is not None:   # ring-staged regions retune too
                ring_specs = [jax.ShapeDtypeStruct(r.shape, r.dtype)
                              for r in region.ring.buffers()]
                for b in sorted(used):
                    region.aot_ring(b, ring_specs)
            # (host staging keeps lazy per-shape jit — it is the
            # measurable seed baseline, not a tuned hot path)
            for parents in region._aot_parents.values():
                n_parent = min(p.shape[0] for p in parents)
                for b in (b for b in sorted(used) if b <= n_parent):
                    region.aot_ref(b, parents)
        # write-back half of the warm-start contract: the tuned state a
        # retune just produced is exactly what process two wants to load
        if self._store is not None and (region.cost.measured()
                                        or region.tuned):
            self._persist_region(region)
            self._store.save()

    def _resweep_chunk(self, region: _Region) -> bool:
        """Retune-time ``inner_chunk="auto"`` re-sweep (supersedes the §9
        warmup-only choice): re-time the chunk candidates on the current
        backend, bypassing the memo.  Returns True when the chunk changed
        — the caller must then treat every compiled program and cost
        sample as stale (this method already resets both)."""
        if not self._chunk_auto:
            return False
        parents = self._primary_parents(region)
        if parents is None:
            return False
        old = region.chunk
        self._tune_chunk(region, parents, force=True)
        if region.chunk == old:
            return False
        region.reset_compiled()
        region.cost.clear()
        region.stats.pop("cost_model", None)
        return True

    @staticmethod
    def _primary_parents(region: _Region) -> Optional[Tuple[Any, ...]]:
        """The parent set measurements run against: the deepest one seen
        (biggest buckets fit), falling back to the ring's buffers."""
        best = None
        for parents in region._aot_parents.values():
            n = min(p.shape[0] for p in parents)
            if best is None or n > best[0]:
                best = (n, parents)
        if best is not None:
            return best[1]
        if region.ring is not None:
            return tuple(jax.ShapeDtypeStruct(r.shape, r.dtype)
                         for r in region.ring.buffers())
        return None

    def _measure_candidates(self, region: _Region
                            ) -> Optional[BucketCostModel]:
        """Time every drain-reachable candidate bucket for the region's
        observed waves (already-measured buckets are free), returning the
        model — or None when nothing could be measured (e.g. a host-staged
        region, which the cost path then treats as launch-count tuning)."""
        cands = sorted(ladder_candidates(region.stats["queue_hist"],
                                         self.config.max_aggregated))
        for parents in region._aot_parents.values():
            self._measure_region(region, cands, parents=parents)
        if region.ring is not None:
            ring_specs = [jax.ShapeDtypeStruct(r.shape, r.dtype)
                          for r in region.ring.buffers()]
            self._measure_region(region, cands, ring_specs=ring_specs)
        return region.cost if region.cost.measured() else None

    def retune(self) -> Dict[str, Tuple[int, ...]]:
        """Force a ladder retune of every region that has completed at
        least one NEW wave since its last retune; returns the ladders by
        family.  A region with an empty queue histogram — or none recorded
        since the last retune — is left untouched: re-deriving from no
        (new) evidence would only produce a degenerate ``(1,)`` ladder or
        burn AOT work reproducing the current one."""
        out = {}
        for region in self._regions.values():
            if (region.stats["queue_hist"]
                    and region.waves != region._retuned_waves):
                region.tuned = False
                self._retune_region(region)
            out[region.signature.describe()] = region.buckets
        return out

    def flush(self) -> None:
        """Launch everything still queued (greedy buckets) and drain.
        Live regions are drained round-robin — one launch per family per
        pass — so interleaved families pipeline on the device."""
        live = [r for r in self._regions.values() if r.queue]
        while live:
            for region in live:
                if region.queue:
                    self._launch(region,
                                 self._largest_bucket(region,
                                                      region.queued_tasks))
            live = [r for r in live if r.queue]
        if self._watchdog_records:
            self._enforce_watchdog()
        self.pool.drain()
        if self._guard_records:
            self._run_guard()
        self._update_breakers()
        # the routing cache holds strong refs to the last wave's parent
        # arrays; the wave is over, release them (next wave re-primes)
        self._sig_cache.clear()

    def map(self, task_args: Sequence[Tuple[Any, ...]],
            kernel: Optional[str] = None) -> List[Any]:
        """Submit many tasks, flush, return their results in order."""
        futs = [self.submit(*a, kernel=kernel) for a in task_args]
        self.flush()
        return [f.result() for f in futs]


# ---------------------------------------------------------------------------
# Region API — the paper's "aggregation region" (a marked code region that
# compatible tasks may enter together).  Cosmetic sugar over the executor.
# ---------------------------------------------------------------------------

_REGIONS: Dict[str, AggregationExecutor] = {}


def aggregation_region(name: str, batched_fn: Callable,
                       config: Optional[AggregationConfig] = None,
                       **kw) -> AggregationExecutor:
    """Get-or-create the named region's executor (one Executor Pool per
    aggregation region, as in the paper's CPPuddle implementation)."""
    exe = _REGIONS.get(name)
    if exe is None:
        exe = AggregationExecutor(batched_fn, config or AggregationConfig(),
                                  name=name, **kw)
        _REGIONS[name] = exe
    return exe


def reset_regions() -> None:
    _REGIONS.clear()
