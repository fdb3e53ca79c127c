"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the Pallas body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention import attention_reference, flash_attention
from repro.kernels.quant_blockwise import (dequantize_reference,
                                           quantize_blockwise,
                                           dequantize_blockwise,
                                           quantize_reference)
from repro.kernels.quant_blockwise.quant_blockwise import (
    dequantize_blockwise_2d, quantize_blockwise_2d)
from repro.kernels.ssd_scan import ssd_reference, ssd_scan

KEY = jax.random.key(7)


# --------------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------------- #
FA_CASES = [
    # (b, s, t, h, kh, d, causal, dtype, bq, bk)
    (2, 128, 128, 4, 2, 64, True, jnp.float32, 64, 64),
    (1, 256, 256, 8, 8, 64, True, jnp.float32, 128, 128),
    (2, 128, 128, 4, 1, 128, False, jnp.float32, 64, 32),
    (1, 128, 128, 2, 2, 64, True, jnp.bfloat16, 64, 64),
    (1, 64, 64, 4, 4, 32, False, jnp.bfloat16, 32, 32),
]


@pytest.mark.parametrize("case", FA_CASES, ids=lambda c: f"s{c[1]}h{c[3]}kh{c[4]}d{c[5]}c{int(c[6])}{c[7].__name__}")
def test_flash_attention_vs_oracle(case):
    b, s, t, h, kh, d, causal, dtype, bq, bk = case
    ks = jax.random.split(jax.random.fold_in(KEY, s * h + d), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, t, kh, d), dtype)
    v = jax.random.normal(ks[2], (b, t, kh, d), dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    want = attention_reference(q, k, v, causal=causal)
    tol = 2.5e-2 if dtype == jnp.bfloat16 else 5e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_grads_flow():
    """The kernel path is differentiable enough for training use? The Pallas
    kernel has no custom VJP — verify the wrapper at least runs under stop-
    gradient-free forward (training uses the XLA path by default)."""
    q = jax.random.normal(KEY, (1, 64, 2, 32))
    k = jax.random.normal(KEY, (1, 64, 2, 32))
    v = jax.random.normal(KEY, (1, 64, 2, 32))
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    assert np.isfinite(np.asarray(out)).all()


# --------------------------------------------------------------------------- #
# ssd scan
# --------------------------------------------------------------------------- #
SSD_CASES = [
    # (b, s, nh, p, g, n, chunk, dtype)
    (2, 128, 8, 32, 1, 16, 64, jnp.float32),
    (1, 256, 4, 16, 2, 8, 32, jnp.float32),
    (1, 64, 2, 64, 1, 32, 64, jnp.float32),
    (2, 128, 4, 32, 1, 16, 32, jnp.bfloat16),
]


@pytest.mark.parametrize("case", SSD_CASES,
                         ids=lambda c: f"s{c[1]}nh{c[2]}p{c[3]}g{c[4]}n{c[5]}c{c[6]}{c[7].__name__}")
def test_ssd_scan_vs_oracle(case):
    b, s, nh, p, g, n, chunk, dtype = case
    ks = jax.random.split(jax.random.fold_in(KEY, s + nh * p), 5)
    x = (jax.random.normal(ks[0], (b, s, nh, p)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    B = (jax.random.normal(ks[3], (b, s, g, n)) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[4], (b, s, g, n)) * 0.3).astype(dtype)
    y1, h1 = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y2, h2 = ssd_reference(x, dt, A, B, C, chunk=chunk)
    scale = float(jnp.max(jnp.abs(y2.astype(jnp.float32)))) + 1e-6
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    assert float(jnp.max(jnp.abs(y1.astype(jnp.float32)
                                 - y2.astype(jnp.float32)))) / scale < tol
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2.reshape(h1.shape)),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-4,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-4)


def test_ssd_scan_with_init_state():
    """Continuation: scan(x[:half]) then scan(x[half:], init_state) == scan(x)."""
    b, s, nh, p, g, n = 1, 128, 4, 16, 1, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, nh, p)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    B = jax.random.normal(ks[3], (b, s, g, n)) * 0.3
    C = jax.random.normal(ks[4], (b, s, g, n)) * 0.3
    y_full, h_full = ssd_scan(x, dt, A, B, C, chunk=32, interpret=True)
    h = s // 2
    y1, h1 = ssd_scan(x[:, :h], dt[:, :h], A, B[:, :h], C[:, :h],
                      chunk=32, interpret=True)
    y2, h2 = ssd_scan(x[:, h:], dt[:, h:], A, B[:, h:], C[:, h:],
                      chunk=32, init_state=h1, interpret=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y_full[:, h:]),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full),
                               rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------- #
# quant blockwise
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("n,d,block,rt", [(64, 512, 128, 32), (256, 256, 256, 256),
                                          (32, 1024, 512, 16)])
def test_quant_2d_vs_oracle(n, d, block, rt):
    x = jax.random.normal(jax.random.fold_in(KEY, n + d), (n, d)) * 3
    q, s = quantize_blockwise_2d(x, block=block, row_tile=rt, interpret=True)
    qr, sr = quantize_reference(x, block=block)
    assert jnp.array_equal(q, qr)
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    xd = dequantize_blockwise_2d(q, s, block=block, row_tile=rt, interpret=True)
    xr = dequantize_reference(qr, sr, block=block)
    np.testing.assert_allclose(np.asarray(xd), np.asarray(xr), rtol=1e-6)


@pytest.mark.parametrize("shape", [(33,), (7, 129), (4, 4, 100), (1000,),
                                   (300 * 256 - 5,)])
def test_quant_roundtrip_error_bound(shape):
    x = jax.random.normal(jax.random.fold_in(KEY, sum(shape)), shape) * 2
    q, s = quantize_blockwise(x, block=256)
    xd = dequantize_blockwise(q, s, tuple(shape), block=256)
    amax = float(jnp.max(jnp.abs(x)))
    assert float(jnp.max(jnp.abs(xd - x))) <= amax / 127 * 0.51 + 1e-6


# --------------------------------------------------------------------------- #
# backend selection
# --------------------------------------------------------------------------- #
def test_auto_interpret_only_on_cpu(monkeypatch):
    """Interpret mode is the CPU test backend's; any backend that is neither
    CPU nor TPU raises instead of silently interpreting."""
    from repro import kernels
    assert kernels.auto_interpret() is True
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: "tpu")
    assert kernels.auto_interpret() is False
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        kernels.auto_interpret()
