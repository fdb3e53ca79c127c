"""GPipe-style pipeline parallelism over a mesh axis (opt-in).

``pipeline`` runs a stack of layers split into P stages along a mesh axis
(typically ``pod``), microbatching the batch dim and rotating activations
between stages with ``jax.lax.ppermute`` — the canonical JAX-native PP
schedule (bubble fraction (P-1)/(M+P-1)).

The wrapper is self-contained shard_map: stage s holds layers
[s*L/P, (s+1)*L/P) (their params sharded over the axis by the leading stage
dim), and at tick t processes microbatch (t - s). Outputs surface on the last
stage and are rotated back to stage 0 so out_specs stay batch-sharded.

Checkpoint math for PP (Eq. (1) of the paper: optimizer state split across
PP ranks) is exercised by ``repro.core.tce.model`` with PP in DP*PP*TP = 8N.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline(layer_fn: Callable, stage_params, x: jax.Array, *,
             mesh: Mesh, axis: str = "pod", n_micro: int = None):
    """Run ``layer_fn(params_i, h) -> h`` for every layer, pipelined.

    stage_params: pytree with leading dim = n_stages (sharded over `axis`),
                  second dim = layers_per_stage.
    x: (batch, ...) global input; batch must divide n_micro * n_stages.
    Returns layer-stack output with the same shape as x.
    """
    n_stages = mesh.shape[axis]
    n_micro = n_micro or n_stages * 2
    b = x.shape[0]
    assert b % n_micro == 0, (b, n_micro)

    def stage_body(params_local, x_local):
        # params_local: (1, layers_per_stage, ...) — this stage's layers
        # x_local: (b/n_stages, ...) — batch shard; gather to full batch of
        # microbatches on stage 0's schedule
        params_local = jax.tree.map(lambda t: t[0], params_local)
        stage = jax.lax.axis_index(axis)
        xs = jax.lax.all_gather(x_local, axis, axis=0, tiled=True)
        micro = xs.reshape((n_micro, b // n_micro) + xs.shape[1:])

        def run_stage(h):
            def body(h_, p_layer):
                return layer_fn(p_layer, h_), None
            h_, _ = jax.lax.scan(body, h, params_local)
            return h_

        n_ticks = n_micro + n_stages - 1
        zero = jnp.zeros_like(micro[0])
        outs = jnp.zeros_like(micro)

        def tick(carry, t):
            h_in, outs_ = carry
            # stage 0 injects microbatch t (if in range); others use received
            inject = jnp.where(t < n_micro, t, 0)
            h = jnp.where(stage == 0,
                          micro[inject],
                          h_in)
            active = (t - stage >= 0) & (t - stage < n_micro)
            h = jnp.where(active, run_stage(h), h)
            # last stage records its finished microbatch (t - (P-1))
            mb = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            record = active & (stage == n_stages - 1)
            outs_ = jnp.where(record,
                              outs_.at[mb].set(h),
                              outs_)
            # rotate forward: stage s -> s+1 (ring; stage P-1 -> 0 unused)
            h_next = jax.lax.ppermute(
                h, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (h_next, outs_), None

        (h_fin, outs), _ = jax.lax.scan(tick, (zero, outs),
                                        jnp.arange(n_ticks))
        # outputs live on the last stage; broadcast so every stage returns
        # its own batch shard
        outs = jax.lax.ppermute(
            outs, axis,
            [(i, (i + 1) % n_stages) for i in range(n_stages)])  # last -> 0
        outs = jax.lax.all_gather(outs, axis, axis=0, tiled=False)
        # after gather: (P, n_micro, mb, ...); stage (P-1)'s outs arrived at
        # slot 0 post-rotation... simpler: take the slot that originated from
        # the last stage: index 0 after the single rotation
        full = outs[0].reshape((b,) + x_local.shape[1:])
        shard = full.reshape((n_stages, b // n_stages) + x_local.shape[1:])
        return shard[stage]

    p_spec = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(stage_body, mesh=mesh,
                       in_specs=(p_spec, P(axis)),
                       out_specs=P(axis), check_vma=False)
    return fn(stage_params, x)
