"""Device executors and the pre-allocated executor pool (CPPuddle analogue).

A ``DeviceExecutor`` is the TPU/XLA analogue of one GPU stream: a handle that
tracks its in-flight launches so the aggregation layer can ask "is this
executor busy?" — the paper's launch criterion for strategy 3.  Under XLA,
dispatch is asynchronous (enqueue returns immediately); an executor is busy
while any of its enqueued launches has not yet produced ready buffers.

The ``ExecutorPool`` mirrors CPPuddle's pre-allocated pool: created once at
startup (stream/executor creation at runtime would synchronize a GPU device;
under XLA the analogous cost is re-tracing/compilation, which the pool also
caches), handed out round-robin or by load.

Hardware-adaptation note (DESIGN.md §2): XLA:TPU runs one kernel at a time
per core, so executors do not add device-side concurrency the way CUDA
streams can on an A100.  They still pipeline host dispatch against device
execution — exactly the regime in which the paper found strategy 2 to be
insufficient on MI100, which we reproduce on this third runtime.
"""
from __future__ import annotations

import itertools
from typing import Any, Callable, List, Optional, Sequence

import jax

from repro.core.trace import span


def _is_ready(x) -> bool:
    """True if a jax array's backing buffer is available (non-blocking)."""
    try:
        return bool(x.is_ready())
    except AttributeError:          # non-jax leaf (python scalar etc.)
        return True


def _is_deleted(x) -> bool:
    """True if a tracked buffer was donated to (consumed by) a later
    launch — e.g. an s2 scatter-ring carry.  Such a buffer is not
    waitable, and needn't be: the chain's liveness rides on the NEWEST
    buffer, which is tracked too."""
    try:
        return bool(x.is_deleted())
    except AttributeError:
        return False


class DeviceExecutor:
    """One launch queue.  Tracks outstanding results for busy-detection."""

    def __init__(self, index: int, max_inflight_tracked: int = 64):
        self.index = index
        self._inflight: List[Any] = []
        self._max_tracked = max_inflight_tracked
        self.launches = 0           # statistics
        self.launches_by_family: dict = {}   # kernel-family tag -> count
        # host time spent enqueueing launches (the ``repro.dispatch`` spans)
        self.stats = {"dispatch_s": 0.0}

    @property
    def dispatch_s(self) -> float:
        return self.stats["dispatch_s"]

    @dispatch_s.setter
    def dispatch_s(self, value: float) -> None:
        self.stats["dispatch_s"] = value

    def launch(self, fn: Callable, *args, family: Optional[str] = None,
               bucket: Optional[int] = None) -> Any:
        """Enqueue fn(*args) (async under XLA) and track its outputs.

        ``family`` tags the launch with its kernel family (TaskSignature
        kernel id) so interleaved multi-region dispatch is observable;
        ``family`` and ``bucket`` (the tasks the launch carries) are the
        ``repro.dispatch`` span's metadata.

        A raising ``fn`` must leave the executor consistent: the host time
        spent before the raise still lands in ``dispatch_s`` (the overhead
        was paid), while the launch counters and in-flight tracking only
        record launches that actually enqueued — a failed dispatch must
        not make ``busy()``/``drain()`` wait on buffers that don't exist.
        """
        with span("repro.dispatch", self.stats, "dispatch_s", kernel=family,
                  bucket=bucket):
            out = fn(*args)
        self.launches += 1
        if family is not None:
            self.launches_by_family[family] = \
                self.launches_by_family.get(family, 0) + 1
        leaves = jax.tree_util.tree_leaves(out)
        if leaves:
            self._inflight.append(leaves[-1])
            if len(self._inflight) > self._max_tracked:
                self._inflight = self._inflight[-self._max_tracked:]
        return out

    def busy(self) -> bool:
        self._inflight = [x for x in self._inflight
                          if not _is_deleted(x) and not _is_ready(x)]
        return bool(self._inflight)

    def drain(self) -> None:
        """Block until every tracked launch is ready.  XLA surfaces
        device-side failures at block time, not at enqueue — so a drain
        must not stop at (or silently swallow) the first bad buffer:
        every buffer is waited on, tracking is always cleared, and the
        FIRST deferred error is re-raised."""
        first: Optional[BaseException] = None
        for x in self._inflight:
            if _is_deleted(x):          # donated to a later launch: skip
                continue
            try:
                jax.block_until_ready(x)
            except Exception as e:      # deferred device-side error
                if first is None:
                    first = e
        self._inflight.clear()
        if first is not None:
            raise first


class ExecutorPool:
    """Pre-allocated pool of executors with round-robin / least-loaded
    scheduling (CPPuddle's ``executor_pool`` analogue)."""

    def __init__(self, n_executors: int = 1, scheduling: str = "round_robin"):
        assert n_executors >= 1
        self.executors = [DeviceExecutor(i) for i in range(n_executors)]
        self.scheduling = scheduling
        self._rr = itertools.cycle(range(n_executors))

    def __len__(self) -> int:
        return len(self.executors)

    def get(self) -> DeviceExecutor:
        if self.scheduling == "load":
            idle = [e for e in self.executors if not e.busy()]
            if idle:
                return idle[0]
        return self.executors[next(self._rr)]

    def any_idle(self) -> bool:
        return any(not e.busy() for e in self.executors)

    def drain(self) -> None:
        """Drain every executor; the first deferred error surfaces after
        ALL executors have been drained (no half-drained pool)."""
        first: Optional[BaseException] = None
        for e in self.executors:
            try:
                e.drain()
            except Exception as err:
                if first is None:
                    first = err
        if first is not None:
            raise first

    @property
    def total_launches(self) -> int:
        return sum(e.launches for e in self.executors)

    @property
    def total_dispatch_s(self) -> float:
        """Aggregate host dispatch wall time (the launch-overhead metric
        reported by benchmarks/launch_overhead.py)."""
        return sum(e.dispatch_s for e in self.executors)

    @property
    def launches_by_family(self) -> dict:
        """Pool-wide launch counts per kernel family tag."""
        out: dict = {}
        for e in self.executors:
            for k, v in e.launches_by_family.items():
                out[k] = out.get(k, 0) + v
        return out
