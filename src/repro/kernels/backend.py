"""The one place that decides how a Pallas kernel runs.

A kernel built here is compiled by Mosaic when its program is lowered for
a TPU, and runs in Pallas interpret mode when it is lowered for any other
platform (XLA:CPU in the tests).  The choice follows the platform of the
lowering, not ``jax.default_backend()``, so a program compiled for a
described TPU topology on a CPU-only host gets the Mosaic kernel too, and
no caller can ask for interpretation on the chip.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def pallas_call(kernel: Callable, *, vmem_limit_bytes: Optional[int] = None,
                **kwargs) -> Callable:
    """``pl.pallas_call(kernel, **kwargs)``: Mosaic on TPU, interpreted
    elsewhere.  ``vmem_limit_bytes`` raises Mosaic's scoped-VMEM limit for
    kernels whose blocks and temporaries exceed the compiler default."""
    params = (pltpu.CompilerParams(vmem_limit_bytes=vmem_limit_bytes)
              if vmem_limit_bytes is not None else None)
    on_tpu = pl.pallas_call(kernel, compiler_params=params, **kwargs)
    elsewhere = pl.pallas_call(kernel, interpret=True, **kwargs)

    def call(*args):
        return jax.lax.platform_dependent(*args, tpu=on_tpu,
                                          default=elsewhere)
    return call
