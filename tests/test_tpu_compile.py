"""Compiles for a described TPU v5e (no chip attached): the kernels of the
main path at real widths and the OLMo-1B train step at full width. Nothing
runs; the chip's compiler accepts or refuses each program, and a Pallas
kernel must come out as a ``tpu_custom_call``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16e9      # one v5e


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("n_blocks", [
    2048 * 8192 // 256,     # OLMo-1B MLP weight (2048, 8192)
    300,                    # more than one row tile, not a whole number
], ids=["olmo_mlp_leaf", "ragged_300_blocks"])
def test_quant_blockwise_compiles(one_chip, n_blocks):
    from repro.kernels.quant_blockwise.ops import (dequantize_blockwise,
                                                   quantize_blockwise)
    n = n_blocks * 256 - 5
    txt = _compiled_text(lambda x: quantize_blockwise(x, interpret=False),
                         _sds(one_chip, (n,)))
    assert "tpu_custom_call" in txt
    txt = _compiled_text(
        lambda q, s: dequantize_blockwise(q, s, (n,), interpret=False),
        _sds(one_chip, (n_blocks, 256), jnp.int8),
        _sds(one_chip, (n_blocks,)))
    assert "tpu_custom_call" in txt


def test_flash_attention_compiles(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention
    qkv = [_sds(one_chip, (4, 2048, 16, 128), jnp.bfloat16)] * 3
    txt = _compiled_text(
        lambda q, k, v: flash_attention(q, k, v, interpret=False), *qkv)
    assert "tpu_custom_call" in txt


def test_ssd_scan_compiles_at_mamba2_130m_widths(one_chip):
    from repro.kernels.ssd_scan.ops import ssd_scan
    b, s, nh, p, n = 4, 2048, 24, 64, 128
    txt = _compiled_text(
        lambda x, dt, A, B, C: ssd_scan(x, dt, A, B, C, chunk=256,
                                        interpret=False),
        _sds(one_chip, (b, s, nh, p)), _sds(one_chip, (b, s, nh)),
        _sds(one_chip, (nh,)), _sds(one_chip, (b, s, 1, n)),
        _sds(one_chip, (b, s, 1, n)))
    assert "tpu_custom_call" in txt


def test_olmo_1b_train_step_compiles_and_fits(one_chip):
    """Full width (d_model 2048, vocab 50304, fp32 state), 2 layers, batch
    4 x 2048: the step the one-chip run takes, at a depth that compiles in
    seconds."""
    from repro.configs import get_config
    from repro.train import (AdamConfig, TrainConfig, make_train_step,
                             train_state_shapes)
    cfg = dataclasses.replace(get_config("olmo-1b"), n_layers=2)
    opt = AdamConfig()
    state = jax.tree.map(lambda s: _sds(one_chip, s.shape, s.dtype),
                         train_state_shapes(cfg, opt))
    batch = {k: _sds(one_chip, (4, 2048), jnp.int32)
             for k in ("tokens", "labels")}
    step = jax.jit(make_train_step(cfg, opt, TrainConfig()),
                   donate_argnums=(0,))
    m = step.lower(state, batch).compile().memory_analysis()
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert m.alias_size_in_bytes > 0.9 * m.argument_size_in_bytes  # donated
    assert need < HBM_BYTES, need
