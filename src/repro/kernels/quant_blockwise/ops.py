"""jit'd wrappers: arbitrary-shape leaves are flattened to (n, d) tiles with
padding; interpret mode on the CPU test backend."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import auto_interpret

from .quant_blockwise import dequantize_blockwise_2d, quantize_blockwise_2d

ROW_TILE = 256


def _row_tile(n_rows: int) -> int:
    return min(ROW_TILE, n_rows)


def _pad_rows(x2: jax.Array) -> jax.Array:
    """Pad (n, d) to whole row tiles: a tile of ROW_TILE rows, or all n."""
    n = x2.shape[0]
    pad = (-n) % _row_tile(n)
    return jnp.pad(x2, ((0, pad), (0, 0))) if pad else x2


def _to_2d(x: jax.Array, block: int) -> jax.Array:
    """Flatten to (n_blocks, block), zero-padding the last block."""
    flat = x.reshape(-1)
    pad = (-flat.shape[0]) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, block)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantize_blockwise(x: jax.Array, block: int = 256,
                       interpret: Optional[bool] = None):
    """Any-shape x -> (q int8 (n_blocks, block), s (n_blocks,))."""
    if interpret is None:
        interpret = auto_interpret()
    x2 = _to_2d(x, block)
    n_blocks = x2.shape[0]
    q, s = quantize_blockwise_2d(_pad_rows(x2), block=block,
                                 row_tile=_row_tile(n_blocks),
                                 interpret=interpret)
    return q[:n_blocks], s[:n_blocks, 0]


@functools.partial(jax.jit, static_argnames=("shape", "block", "dtype", "interpret"))
def dequantize_blockwise(q: jax.Array, s: jax.Array, shape,
                         block: int = 256, dtype=jnp.float32,
                         interpret: Optional[bool] = None):
    if interpret is None:
        interpret = auto_interpret()
    n_blocks = q.shape[0]
    x2 = dequantize_blockwise_2d(_pad_rows(q), _pad_rows(s[:, None]),
                                 block=block, row_tile=_row_tile(n_blocks),
                                 dtype=dtype, interpret=interpret)
    n = 1
    for d in shape:
        n *= d
    return x2.reshape(-1)[:n].reshape(shape)
