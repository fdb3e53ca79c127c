"""Abstract input stand-ins (ShapeDtypeStruct) per (arch x shape) cell.

Weak-type-correct, shardable, zero allocation — the dry-run lowers against
these. Stub frontends (whisper frames, qwen2-vl patches) are expressed here as
precomputed embeddings, per the assignment.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ShapeSpec
from repro.models import ModelConfig, blocks

I32 = jnp.int32


def batch_specs(cfg: ModelConfig, b: int, s: int, with_labels: bool):
    shapes: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((b, s), I32),
    }
    axes: Dict[str, Any] = {"tokens": ("batch", None)}
    if with_labels:
        shapes["labels"] = jax.ShapeDtypeStruct((b, s), I32)
        axes["labels"] = ("batch", None)
    if cfg.family == "encdec":
        shapes["enc_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.encdec.enc_len, cfg.d_model), jnp.dtype(cfg.compute_dtype))
        axes["enc_embeds"] = ("batch", None, None)
    if cfg.family == "vlm":
        nv = min(cfg.vlm.n_vision_tokens, s)
        shapes["vision_embeds"] = jax.ShapeDtypeStruct(
            (b, nv, cfg.d_model), jnp.dtype(cfg.compute_dtype))
        axes["vision_embeds"] = ("batch", None, None)
    return shapes, axes


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Returns (abstract_inputs, logical_axes) for the cell's step function.

    train   -> {'batch': ...}
    prefill -> {'batch': ...}
    decode  -> {'token', 'cache', 'pos'}
    """
    b, s = shape.global_batch, shape.seq_len
    if shape.kind in ("train", "prefill"):
        shapes, axes = batch_specs(cfg, b, s, with_labels=(shape.kind == "train"))
        return {"batch": shapes}, {"batch": axes}
    # decode: one new token against a cache of length s
    enc_len = cfg.encdec.enc_len if cfg.encdec else None
    cache = blocks.cache_struct(cfg, b, s, enc_len=enc_len, mode="shape")
    cache_axes = blocks.cache_struct(cfg, b, s, enc_len=enc_len, mode="axes")
    return ({"token": jax.ShapeDtypeStruct((b,), I32),
             "cache": cache,
             "pos": jax.ShapeDtypeStruct((b,), I32)},
            {"token": ("batch",), "cache": cache_axes, "pos": ("batch",)})
