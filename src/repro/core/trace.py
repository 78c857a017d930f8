"""Host spans and program names on the JAX profiler's clock.

``span`` brackets one piece of host work.  It enters a
``jax.profiler.TraceAnnotation``, which the profiler records only while a
trace runs, on the same host plane and clock as the device's operations,
and which is a no-op otherwise.  Given a ``stats`` dict and a ``key``, it
also adds its ``perf_counter`` duration to ``stats[key]``, trace or no
trace: a counter and a span then come from one bracket.  The program's
spans are named ``repro.<what>``; metadata are keyword arguments, which
the profiler keeps as the event's stats, not in its name.

``named`` gives a jitted program a stable name: ``jax.jit`` of a callable
named ``hydro_rhs_b32`` compiles a module ``jit_hydro_rhs_b32``, which is
what the device trace calls each of its operations' program.

``kernel_traces`` (kept by ``repro.kernels.backend``) counts, per process
and kernel name, how often a Pallas kernel body was traced into a jaxpr:
set-up work that a warm compile cache does not save, so a kernel that is
traced again for every program that calls it shows here.
"""
from __future__ import annotations

import time
from typing import Callable, Dict, Optional

from jax.profiler import TraceAnnotation

from repro.kernels.backend import kernel_traces  # noqa: F401


class span:
    """``with span("repro.dispatch", kernel=k, bucket=b): ...``; with
    ``stats`` and ``key`` the duration also lands in ``stats[key]``, also
    when the body raises (the time was spent)."""

    __slots__ = ("_annotation", "_stats", "_key", "_t0")

    def __init__(self, name: str, stats: Optional[Dict[str, float]] = None,
                 key: Optional[str] = None, **meta):
        self._annotation = TraceAnnotation(name, **meta)
        self._stats, self._key = stats, key

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        if self._stats is not None:
            self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        if self._stats is not None:
            self._stats[self._key] += time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)


def program_name(kernel: str, bucket: Optional[int] = None) -> str:
    """``hydro_rhs+epi``, 32 -> ``hydro_rhs_epi_b32``: the name of a kernel
    family's program, per bucket where the program serves one."""
    name = kernel.replace("+", "_")
    return name if bucket is None else f"{name}_b{bucket}"


def named(fn: Callable, name: str) -> Callable:
    """``fn`` under the function name ``name`` (``fn`` itself, often a
    ``functools.partial`` or a body shared by several programs, is left
    as it is).  Only the name of the program changes, never its HLO."""
    def program(*args):
        return fn(*args)
    program.__name__ = program.__qualname__ = name
    return program
