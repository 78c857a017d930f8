"""The one place that decides how a Pallas kernel runs.

A kernel built here is compiled by Mosaic when its program is lowered for
a TPU, and runs in Pallas interpret mode when it is lowered for any other
platform (XLA:CPU in the tests).  The choice follows the platform of the
lowering, not ``jax.default_backend()``, so a program compiled for a
described TPU topology on a CPU-only host gets the Mosaic kernel too, and
no caller can ask for interpretation on the chip.

``kernel_traces`` counts, per process and kernel name, how often a kernel
body was traced into a jaxpr: once for the Mosaic and once for the
interpret branch each time a program that calls the kernel is traced.
Tracing is set-up work that a warm compile cache does not save (a program
is traced and lowered before the cache is looked up).
"""
from __future__ import annotations

import collections
from typing import Callable, Optional

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

kernel_traces: collections.Counter[str] = collections.Counter()


def pallas_call(kernel: Callable, *, vmem_limit_bytes: Optional[int] = None,
                **kwargs) -> Callable:
    """``pl.pallas_call(kernel, **kwargs)``: Mosaic on TPU, interpreted
    elsewhere.  ``vmem_limit_bytes`` raises Mosaic's scoped-VMEM limit for
    kernels whose blocks and temporaries exceed the compiler default.
    Traces count in ``kernel_traces`` under ``kwargs["name"]``, else the
    kernel function's name."""
    name = kwargs.get("name") or getattr(kernel, "func", kernel).__name__

    def counted(*refs):
        kernel_traces[name] += 1
        return kernel(*refs)

    params = (pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes)
              if vmem_limit_bytes is not None else None)
    on_tpu = pl.pallas_call(counted, compiler_params=params, **kwargs)
    elsewhere = pl.pallas_call(counted, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=on_tpu,
                                          default=elsewhere)
    return call
