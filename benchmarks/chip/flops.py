"""Model FLOPs of one training step, counted from the configuration's sizes.

The PaLM convention (Chowdhery et al. 2022, appendix B): per token, 6 x the
parameters of every matrix product (forward 2, backward 4) plus the
attention scores and their values, 12 x layers x (heads x head size) x
sequence length, as if every query saw every key. Recomputation under
rematerialisation is not counted. The embedding lookup is not a product;
with tied embeddings the output head is, and counts once.
"""
from __future__ import annotations


def matmul_params(model: dict) -> int:
    """Parameters that take part in a matrix product per token."""
    d, f, v = model["d_model"], model["d_ff"], model["vocab_size"]
    hq = model["n_heads"] * model["d_head"]
    hkv = model["n_kv_heads"] * model["d_head"]
    mlp = (3 if model.get("activation", "swiglu") == "swiglu" else 2) * d * f
    per_layer = d * hq + 2 * d * hkv + hq * d + mlp
    return model["n_layers"] * per_layer + v * d


def train_step_flops(model: dict, batch: int, seq: int) -> float:
    attn = 12 * model["n_layers"] * model["n_heads"] * model["d_head"] * seq
    return float(batch * seq * (6 * matmul_params(model) + attn))
