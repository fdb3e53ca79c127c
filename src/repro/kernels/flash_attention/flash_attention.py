"""Blocked online-softmax attention (FlashAttention-2 schedule) for TPU.

Grid: (batch, q_heads, q_blocks, kv_blocks) — kv_blocks is the `arbitrary`
(sequential) dimension; running max/denominator/accumulator live in VMEM
scratch across kv iterations. BlockSpecs tile Q/K/V so the working set is
(bq x d) + 2 x (bk x d) + (bq x bk) — VMEM-resident, MXU-aligned when bq, bk,
d are multiples of 128 (8 for fp32 sublanes). GQA is handled by indexing the
kv head as h // (H // KH) in the K/V BlockSpecs.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref,
               m_scr, l_scr, acc_scr,
               *, scale: float, causal: bool, bq: int, bk: int, nk: int):
    i = pl.program_id(2)          # q block
    j = pl.program_id(3)          # kv block

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)               # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)               # (bk, d)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_idx = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_idx = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(q_idx >= k_idx, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(j == nk - 1)
    def _finish():
        denom = jnp.maximum(l_scr[...], 1e-20)
        o_ref[0, 0] = (acc_scr[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array,
                         *, causal: bool = True,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False) -> jax.Array:
    """q: (B, H, S, D); k, v: (B, KH, T, D). Returns (B, H, S, D)."""
    b, h, s, d = q.shape
    kh, t = k.shape[1], k.shape[2]
    rep = h // kh
    bq = min(block_q, s)
    bk = min(block_k, t)
    assert s % bq == 0 and t % bk == 0, (s, bq, t, bk)
    nq, nk = s // bq, t // bk
    scale = 1.0 / (d ** 0.5)

    kernel = functools.partial(_fa_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk)
    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_ // rep, j, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda b_, h_, i, j: (b_, h_ // rep, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
