"""Analytical roofline prior for unmeasured bucket costs (DESIGN.md §13).

With an empty store, the measured tuner's only option used to be timing
every candidate bucket at warmup.  The prior replaces that first contact
with arithmetic: a bucket-``b`` launch of a kernel family is modeled as

    t(b) = t_launch + max(bytes_moved(b) / BW_peak,  flops(b) / FLOPs_peak)

— the classic roofline, plus the constant per-launch overhead that the
whole aggregation ladder exists to amortize.  ``bytes_moved`` comes from
the family's argument shapes/dtypes (inputs read + ``jax.eval_shape``'d
outputs written, scaled by the bucket); ``flops`` comes from XLA's own
cost analysis of the bucket-1 program when available (one lowering, zero
launches), falling back to a fixed arithmetic-intensity guess.  Device
peaks come from a small table keyed by exact ``device_kind``; unknown devices
get a measured-once micro-benchmark (one bandwidth op, one matmul, one
empty launch — memoized for the process).

The absolute numbers only need to be roughly right: ``derive_ladder``
consumes RATIOS between bucket sizes, and any model of the form
``overhead + monotone traffic`` already encodes the paper's core fact —
few big launches beat many small ones — which is what makes the
prior-seeded ladder sane before the first real wave.  Every seeded entry
is tagged ``source="prior"`` in the cost model and evicted the moment
``retune()`` measures for real.
"""
from __future__ import annotations

import math
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

# exact ``device_kind`` -> (bytes/s, flop/s, launch seconds), each row with
# its source.  A kind that is not here (another TPU generation, a GPU) is
# calibrated once by the micro-benchmark below instead of borrowing a
# neighbour's peaks.  "cpu" stays in the table so CPU-only CI never pays
# the calibration run.
DEVICE_PEAKS: Dict[str, Tuple[float, float, float]] = {
    # XLA:CPU host: a coarse sustained-streaming estimate, not a datasheet
    # figure (the prior needs the ladder's shape, not absolute time)
    "cpu":         (2.0e10, 5.0e10, 2.0e-5),
    # TPU v5e: Google Cloud documentation "TPU v5e" -- 819 GB/s HBM,
    # 197 TFLOP/s bf16 (the MXU peak; f32 vector-unit stencils run far
    # below it).  Launch overhead is an estimate, not measured.
    "TPU v5 lite": (8.19e11, 1.97e14, 5.0e-5),
}

# flops per element when XLA's cost analysis is unavailable: a band
# between pure-streaming (≈1) and stencil/PPM-style bodies (tens)
FALLBACK_FLOPS_PER_ELEM = 16.0

# measured-once calibration memo: backend key -> (bw, flops, launch)
_CALIBRATION: Dict[Tuple[str, str], Tuple[float, float, float]] = {}


def _microbenchmark() -> Tuple[float, float, float]:
    """Measure this device once: streaming bandwidth from a large
    elementwise sum, FLOP throughput from a matmul, launch overhead from
    a no-op-sized program.  Medians of a handful of runs — calibration
    happens once per process per unknown device, so a second of timing
    is acceptable where per-bucket timing at every warmup was not."""
    import jax
    import jax.numpy as jnp

    def timed(fn, *args, runs=5):
        jax.block_until_ready(fn(*args))          # compile + warm
        ts = []
        for _ in range(runs):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        ts.sort()
        return ts[len(ts) // 2]

    n = 1 << 22                                   # 16 MiB of f32
    x = jnp.zeros((n,), jnp.float32)
    t_bw = timed(jax.jit(lambda a: a * 2.0 + 1.0), x)
    bw = (2 * n * 4) / max(t_bw, 1e-9)            # one read + one write

    m = 512
    a = jnp.zeros((m, m), jnp.float32)
    t_mm = timed(jax.jit(lambda p, q: p @ q), a, a)
    flops = (2.0 * m ** 3) / max(t_mm, 1e-9)

    t_launch = timed(jax.jit(lambda s: s + 1.0), jnp.float32(0.0))
    return bw, flops, max(t_launch, 1e-7)


def device_peaks(backend_key: Tuple[str, ...]) -> Tuple[float, float, float]:
    """(bytes/s, flop/s, launch seconds) for the keyed device: table hit
    by exact ``device_kind``, else the memoized micro-benchmark."""
    known = DEVICE_PEAKS.get(backend_key[1])
    if known is not None:
        return known
    cal = _CALIBRATION.get(backend_key)
    if cal is None:
        cal = _CALIBRATION[backend_key] = _microbenchmark()
    return cal


class RooflinePrior:
    """Seconds-per-launch estimates for one process's device, computed
    from shapes instead of stopwatches.  Stateless apart from per-family
    flop-count and output-spec memos (keyed on the body's identity plus
    the task specs, mirroring the chunk-tune memo's keying rationale)."""

    def __init__(self, backend_key: Optional[Tuple[str, ...]] = None):
        if backend_key is None:
            import jax
            try:
                kind = getattr(jax.devices()[0], "device_kind", "")
                topo = f"d{len(jax.devices())}"
            except RuntimeError:
                kind, topo = "", "d0"
            backend_key = (jax.default_backend(), kind, topo)
        self.backend_key = backend_key
        self.bandwidth, self.peak_flops, self.launch_overhead = \
            device_peaks(backend_key)
        # (body id, task specs) -> (flops per task, out bytes per task);
        # the body ref rides along to keep id() valid (cf. _CHUNK_TUNE_MEMO)
        self._family_memo: Dict[Tuple, Tuple[Any, float, float]] = {}

    # -- per-family analysis -----------------------------------------------
    @staticmethod
    def _spec_key(task_specs: Sequence[Any]) -> Tuple:
        return tuple((tuple(s.shape), np.dtype(s.dtype).str)
                     for s in task_specs)

    @staticmethod
    def _nbytes(shape: Sequence[int], dtype: Any) -> float:
        return float(math.prod(shape) * np.dtype(dtype).itemsize)

    def _analyze_family(self, batched_fn: Any,
                        task_specs: Sequence[Any]) -> Tuple[float, float]:
        """(flops, output bytes) for ONE task of this family."""
        key = (id(batched_fn), self._spec_key(task_specs))
        memo = self._family_memo.get(key)
        if memo is not None:
            return memo[1], memo[2]
        import jax

        b1 = tuple(jax.ShapeDtypeStruct((1,) + tuple(s.shape), s.dtype)
                   for s in task_specs)
        in_elems = sum(math.prod(s.shape) for s in task_specs)
        try:
            out = jax.eval_shape(batched_fn, *b1)
            leaves = jax.tree_util.tree_leaves(out)
            out_bytes = sum(self._nbytes(l.shape, l.dtype) for l in leaves)
            out_elems = sum(math.prod(l.shape) for l in leaves)
        except (TypeError, ValueError):
            # body rejects a bucket-1 batch (e.g. fixed-wave-only fused
            # twin): charge it as write-what-you-read streaming
            out_bytes = sum(self._nbytes(s.shape, s.dtype)
                            for s in task_specs)
            out_elems = in_elems
        flops = self._xla_flops(batched_fn, b1)
        if flops is None:
            flops = FALLBACK_FLOPS_PER_ELEM * max(in_elems, out_elems, 1)
        self._family_memo[key] = (batched_fn, float(flops), out_bytes)
        return float(flops), out_bytes

    @staticmethod
    def _xla_flops(batched_fn: Any, b1_specs: Tuple) -> Optional[float]:
        """XLA's own FLOP count of the bucket-1 program — a lowering plus
        cost analysis, never an execution.  None when the backend or body
        does not support it (the caller then falls back to the
        intensity guess)."""
        import jax
        try:
            analysis = jax.jit(batched_fn).lower(*b1_specs).cost_analysis()
        except Exception:
            return None
        if isinstance(analysis, (list, tuple)):       # older jax returns
            analysis = analysis[0] if analysis else None  # one per device
        if not isinstance(analysis, dict):
            return None
        flops = analysis.get("flops")
        if flops is None or not np.isfinite(flops) or flops < 0:
            return None
        return float(flops)

    # -- the prediction ----------------------------------------------------
    def predict(self, batched_fn: Any, task_specs: Sequence[Any],
                bucket: int) -> float:
        """Predicted seconds for ONE launch of a ``bucket``-task program
        of this family: launch overhead + roofline of the bucket's
        traffic.  Per-task flops/bytes scale linearly in the bucket —
        exact for the elementwise-over-slots bodies aggregation accepts."""
        flops1, out_bytes1 = self._analyze_family(batched_fn, task_specs)
        in_bytes1 = sum(self._nbytes(s.shape, s.dtype) for s in task_specs)
        b = max(1, int(bucket))
        bytes_moved = b * (in_bytes1 + out_bytes1)
        flops = b * flops1
        return self.launch_overhead + max(bytes_moved / self.bandwidth,
                                          flops / self.peak_flops)
