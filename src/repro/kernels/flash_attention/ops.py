"""jit'd public wrapper for the flash-attention kernel.

Accepts the model-layout (B, S, H, D) / (B, T, KH, D) tensors, transposes to
the kernel layout, and selects interpret mode on the CPU test backend (the
kernel body then executes in Python for validation)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret

from .flash_attention import flash_attention_bhsd


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, S, H, D); k, v: (B, T, KH, D) -> (B, S, H, D)."""
    interpret = auto_interpret() if interpret is None else interpret
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    bq = min(block_q, q.shape[1])
    bk = min(block_k, k.shape[1])
    out = flash_attention_bhsd(qt, kt, vt, causal=causal,
                               block_q=bq, block_k=bk, interpret=interpret)
    return jnp.swapaxes(out, 1, 2)
