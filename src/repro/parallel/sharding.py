"""Logical-axis sharding rules.

Parameters and activations are annotated with *logical* axis names (see
``repro.models.layers``); this module maps them onto mesh axes and applies
``with_sharding_constraint`` only when a sharding context is active — CPU
smoke tests run with no mesh and every helper degrades to a no-op.

Legality is enforced structurally: for every array dim we keep only mesh axes
that (a) divide the dim and (b) are not already used by an earlier dim of the
same array ("first-wins"), so any rule table produces a valid PartitionSpec
for any shape. Dropped axes simply mean replication — visible in the roofline,
never an error.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisRule = Union[None, str, Tuple[str, ...]]

# ---------------------------------------------------------------------------
# Rule tables
# ---------------------------------------------------------------------------
# Parameters: 2D sharded — FSDP over `data` on the embed axis, TP/EP over
# `model` on heads/mlp/vocab/experts. Replicated across `pod` (gradients are
# all-reduced — optionally compressed — on the pod axis).
PARAM_RULES: Dict[str, AxisRule] = {
    "embed": "data",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "vocab": "model",
    "experts": "model",
    "lora": None,
    "layers": None,
    "mlp_fsdp": "data",      # MoE expert FFN hidden dim (see moe_params)
}

# Activations.
ACT_RULES: Dict[str, AxisRule] = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_sp": "model",                  # sequence-parallel sections
    "act_heads": "model",
    "act_kv_heads": "model",
    "act_embed": None,
    "act_mlp": "model",
    "act_vocab": "model",
    "cache_seq": ("pod", "data"),       # used when batch is unshardable (b=1)
    "moe_groups": ("pod", "data", "model"),
    "moe_groups_dp": ("pod", "data"),
    "moe_experts": "model",
    "state_heads": "model",
}

DEFAULT_RULES: Dict[str, AxisRule] = {**PARAM_RULES, **ACT_RULES}

# ---------------------------------------------------------------------------
# Presets (hillclimb levers)
# ---------------------------------------------------------------------------
# megatron (default): 2D param sharding — FSDP over data on embed, TP/EP over
#   model on heads/mlp/vocab/experts; batch over (pod, data).
# fsdp: ZeRO-3-pure — every param sharded over (data, model) on its embed
#   axis, batch over the whole mesh, no tensor-parallel activations. Right
#   for models whose per-layer compute is too small to amortise TP
#   all-reduces (<= ~10B dense at 4k tokens/device).
FSDP_RULES: Dict[str, AxisRule] = {
    **DEFAULT_RULES,
    "embed": ("data", "model"),
    "heads": None,
    "kv_heads": None,
    "mlp": None,
    "vocab": None,
    "batch": ("pod", "data", "model"),
    "act_heads": None,
    "act_kv_heads": None,
    "act_mlp": None,
    "act_vocab": None,
    "moe_groups": ("pod", "data", "model"),
    "moe_groups_dp": ("pod", "data", "model"),
    "moe_experts": None,
}

# megatron_sp: megatron + sequence parallelism on the residual stream — the
# seq dim of activations shards over 'model' between blocks (Korthikanti'22),
# shrinking remat-saved activations and the shard_map MoE boundary reshard by
# the TP degree.
MEGATRON_SP_RULES: Dict[str, AxisRule] = {**DEFAULT_RULES, "seq": "model"}

RULES_PRESETS: Dict[str, Dict[str, AxisRule]] = {
    "megatron": DEFAULT_RULES,
    "megatron_sp": MEGATRON_SP_RULES,
    "fsdp": FSDP_RULES,
}


class ShardingContext:
    def __init__(self, mesh: Mesh, rules: Optional[Dict[str, AxisRule]] = None):
        self.mesh = mesh
        self.rules = dict(DEFAULT_RULES if rules is None else rules)

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size


_tls = threading.local()


def active() -> Optional[ShardingContext]:
    return getattr(_tls, "ctx", None)


@contextlib.contextmanager
def use_sharding(mesh: Mesh, rules: Optional[Dict[str, AxisRule]] = None):
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ShardingContext(mesh, rules)
    try:
        yield _tls.ctx
    finally:
        _tls.ctx = prev


# ---------------------------------------------------------------------------
# Spec construction
# ---------------------------------------------------------------------------
def _as_tuple(rule: AxisRule) -> Tuple[str, ...]:
    if rule is None:
        return ()
    if isinstance(rule, str):
        return (rule,)
    return tuple(rule)


def spec_for(axes: Sequence[Optional[str]], shape: Sequence[int],
             ctx: Optional[ShardingContext] = None) -> P:
    """Build a legal PartitionSpec for `shape` from logical `axes`."""
    ctx = ctx or active()
    if ctx is None:
        return P()
    mesh_shape = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    used: set = set()
    dims = []
    for name, size in zip(axes, shape):
        chosen = []
        for ax in _as_tuple(ctx.rules.get(name)) if name else ():
            if ax in used or ax not in mesh_shape:
                continue
            prod = 1
            for c in chosen:
                prod *= mesh_shape[c]
            if size % (prod * mesh_shape[ax]) == 0:
                chosen.append(ax)
                used.add(ax)
        if not chosen:
            dims.append(None)
        elif len(chosen) == 1:
            dims.append(chosen[0])
        else:
            dims.append(tuple(chosen))
    while dims and dims[-1] is None:
        dims.pop()
    return P(*dims)


def constrain(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """Apply a sharding constraint when a context is active; else identity."""
    ctx = active()
    if ctx is None:
        return x
    spec = spec_for(axes, x.shape, ctx)
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


def tree_shardings(axes_tree, shape_tree, mesh: Mesh,
                   rules: Optional[Dict[str, AxisRule]] = None):
    """NamedSharding tree for (axes, ShapeDtypeStruct) trees — pjit in_shardings."""
    ctx = ShardingContext(mesh, rules)

    def one(axes, sds):
        return NamedSharding(mesh, spec_for(axes, sds.shape, ctx))

    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(a, (str, type(None))) for a in x))


def tree_specs(axes_tree, shape_tree, mesh: Mesh,
               rules: Optional[Dict[str, AxisRule]] = None):
    """PartitionSpec tree (for printing / tests)."""
    ctx = ShardingContext(mesh, rules)

    def one(axes, sds):
        return spec_for(axes, sds.shape, ctx)

    return jax.tree.map(one, axes_tree, shape_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(a, (str, type(None))) for a in x))
