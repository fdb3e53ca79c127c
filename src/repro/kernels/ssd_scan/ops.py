"""jit'd public wrapper for the SSD scan kernel (interpret mode on the CPU
test backend)."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import auto_interpret

from .ssd_scan import ssd_scan_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, A: jax.Array,
             B: jax.Array, C: jax.Array, chunk: int = 128,
             init_state: Optional[jax.Array] = None,
             interpret: Optional[bool] = None):
    """Same contract as repro.models.ssm.ssd_chunked."""
    if interpret is None:
        interpret = auto_interpret()
    return ssd_scan_pallas(x, dt, A, B, C, chunk=chunk,
                           init_state=init_state, interpret=interpret)
