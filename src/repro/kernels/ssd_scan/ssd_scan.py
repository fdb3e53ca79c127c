"""Mamba-2 SSD chunked scan as a Pallas TPU kernel.

Grid: (batch, n_chunks); chunks are the sequential (`arbitrary`) dimension
with the inter-chunk SSM state carried in VMEM scratch — the TPU-native
re-blocking of the GPU scan: intra-chunk terms are dense (c x c) and
(c x p x n) contractions that map onto the MXU, the recurrence touches VMEM
only once per chunk.

The kernel works head-major: the wrapper lays x out as (b, nh, s, p) and
B/C as (b, g, s, n), so every per-head operand is a 2-D (rows, lanes) tile
and every contraction is a plain 2-D matmul, the forms Mosaic lowers. The
chunk-local prefix sum of dt*A is a matmul against a lower-triangular ones
matrix (Mosaic has no cumsum).

Working set per grid step (c=256, nh=24, p=64, n=128):
  x/dt/B/C blocks + one (c, c) decay matrix + (nh, p, n) state  <~ 4 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, h0_ref,
                y_ref, hf_ref, state_scr, *, n_chunks: int, rep: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        state_scr[...] = h0_ref[0].astype(F32)

    dt = dt_ref[0].astype(F32)                # (c, nh)
    c, nh = dt.shape
    p = x_ref.shape[3]
    tri = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)    # j' <= i
    # inclusive prefix sum of dt*A over the chunk
    cum = jnp.dot(tri.astype(F32), dt * a_ref[...].astype(F32),
                  precision=jax.lax.Precision.HIGHEST,
                  preferred_element_type=F32)             # (c, nh)
    cum_t = cum.T                                         # (nh, c)
    # every row = cum[c-1]: the chunk's total decay exponent per head, as
    # rows to slice columns from (Mosaic cannot broadcast a (1, 1) value)
    r = max(c, p)
    last = jax.lax.broadcasted_iota(jnp.int32, (r, c), 1) == c - 1
    cum_end = jnp.dot(last.astype(F32), cum,
                      precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=F32)         # (r, nh)

    for h in range(nh):
        g = h // rep
        Bg = b_ref[0, g].astype(F32)                      # (c, n)
        Cg = c_ref[0, g].astype(F32)                      # (c, n)
        cum_col = cum[:, h:h + 1]                         # (c, 1)
        xdt = x_ref[0, h].astype(F32) * dt[:, h:h + 1]    # (c, p)

        # L[i, j'] = exp(cum[i] - cum[j']) masked to j' <= i
        L = jnp.where(tri, jnp.exp(cum_col - cum_t[h:h + 1, :]), 0.0)
        CB = jax.lax.dot_general(Cg, Bg, _NT, preferred_element_type=F32)
        y_intra = jnp.dot(CB * L, xdt, preferred_element_type=F32)

        state = state_scr[h]                              # (p, n)
        y_inter = jax.lax.dot_general(Cg, state, _NT,
                                      preferred_element_type=F32) \
            * jnp.exp(cum_col)                            # (c, p)
        y_ref[0, h] = (y_intra + y_inter).astype(y_ref.dtype)

        ddec = jnp.exp(cum_end[:c, h:h + 1] - cum_col)    # (c, 1)
        s_new = jax.lax.dot_general(xdt * ddec, Bg, _TN,
                                    preferred_element_type=F32)
        state_scr[h] = state * jnp.exp(cum_end[:p, h:h + 1]) + s_new

    @pl.when(j == n_chunks - 1)
    def _finish():
        hf_ref[0] = state_scr[...]


def ssd_scan_pallas(x: jax.Array, dt: jax.Array, A: jax.Array,
                    B: jax.Array, C: jax.Array, *, chunk: int = 128,
                    init_state=None, interpret: bool = False):
    """x: (b, s, nh, p); dt: (b, s, nh); A: (nh,); B, C: (b, s, g, n).
    Returns (y: (b, s, nh, p), final_state: (b, nh, p, n) f32)."""
    b, s, nh, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = nh // g
    c = min(chunk, s)
    assert s % c == 0, (s, c)
    nc = s // c
    if init_state is None:
        init_state = jnp.zeros((b, nh, p, n), jnp.float32)

    kernel = functools.partial(_ssd_kernel, n_chunks=nc, rep=rep)
    y_t, hf = pl.pallas_call(
        kernel,
        grid=(b, nc),
        in_specs=[
            pl.BlockSpec((1, nh, c, p), lambda b_, j: (b_, 0, j, 0)),
            pl.BlockSpec((1, c, nh), lambda b_, j: (b_, j, 0)),
            pl.BlockSpec((1, nh), lambda b_, j: (0, 0)),
            pl.BlockSpec((1, g, c, n), lambda b_, j: (b_, 0, j, 0)),
            pl.BlockSpec((1, g, c, n), lambda b_, j: (b_, 0, j, 0)),
            pl.BlockSpec((1, nh, p, n), lambda b_, j: (b_, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, nh, c, p), lambda b_, j: (b_, 0, j, 0)),
            pl.BlockSpec((1, nh, p, n), lambda b_, j: (b_, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nh, s, p), x.dtype),
            jax.ShapeDtypeStruct((b, nh, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((nh, p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(jnp.transpose(x, (0, 2, 1, 3)), dt, A.reshape(1, nh),
      jnp.transpose(B, (0, 2, 1, 3)), jnp.transpose(C, (0, 2, 1, 3)),
      init_state)
    return jnp.transpose(y_t, (0, 2, 1, 3)), hf
