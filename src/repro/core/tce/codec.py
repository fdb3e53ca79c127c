"""Checkpoint payload codecs for the NAS persist / ring-backup flows.

The paper's measured NAS link (71.1 MB/s per rank) is the modelled bottleneck
of the whole checkpoint datapath, so shrinking the bytes that cross it cuts
modelled persist/restore time proportionally. Three encodings:

* ``raw``  — bytes as-is (the default; bit-exact, zero transform cost).
* ``zlib`` — lossless DEFLATE. Bit-exact on decode; falls back to ``raw``
  when a payload is incompressible (random-looking fp32 noise can expand).
* ``int8`` — blockwise symmetric absmax quantisation through the existing
  Pallas ``quant_blockwise`` kernel (interpret mode on the CPU). ~4x smaller
  for fp32 leaves, lossy within the kernel's per-block scale tolerance.
  Non-float leaves and **lossless-allowlisted paths** (optimizer-critical
  state) are never quantised — they silently take the ``zlib`` lossless
  route instead.

``encode_shard``/``decode_shard`` are pure byte transforms: callers own
policy (which codec, which paths stay lossless) and accounting.
"""
from __future__ import annotations

import fnmatch
import zlib
from typing import Dict, Optional, Tuple

import numpy as np

CODECS = ("raw", "zlib", "int8")
INT8_BLOCK = 256
_QUANT_DTYPES = ("float32", "float16", "bfloat16", "float64")


def is_lossless_path(path: str, patterns: Tuple[str, ...]) -> bool:
    """fnmatch-style allowlist for leaves that must stay bit-exact."""
    return any(fnmatch.fnmatch(path, p) for p in patterns)


def _flat_u8(data: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(data).view(np.uint8).reshape(-1)


def encode_shard(data: np.ndarray, codec: str, *, lossless: bool = False,
                 block: int = INT8_BLOCK) -> Tuple[str, np.ndarray, Dict]:
    """Encode one shard's bytes. Returns ``(enc, payload_u8, meta)``.

    ``enc`` is the encoding actually used (int8 demotes to zlib for
    lossless/non-float leaves; zlib demotes to raw when incompressible).
    """
    if codec not in CODECS:
        raise ValueError(f"unknown codec {codec!r} (want one of {CODECS})")
    data = np.ascontiguousarray(data)
    if data.size == 0:
        return "raw", _flat_u8(data), {}
    if codec == "int8" and (lossless or str(data.dtype) not in _QUANT_DTYPES):
        codec = "zlib"
    if codec == "raw":
        return "raw", _flat_u8(data), {}
    if codec == "zlib":
        comp = zlib.compress(memoryview(data).cast("B"), 1)
        if len(comp) >= data.nbytes:          # incompressible: keep raw
            return "raw", _flat_u8(data), {}
        return "zlib", np.frombuffer(comp, np.uint8), {}
    # int8 blockwise quantisation through the Pallas kernel
    import jax.numpy as jnp
    from repro.kernels.quant_blockwise.ops import quantize_blockwise
    q, s = quantize_blockwise(jnp.asarray(data, jnp.float32), block=block)
    q_np, s_np = np.asarray(q), np.asarray(s, np.float32)
    payload = np.concatenate([q_np.reshape(-1).view(np.uint8),
                              s_np.view(np.uint8)])
    return "int8", payload, {"block": block, "n_blocks": int(q_np.shape[0])}


def decode_shard(enc: str, payload: np.ndarray, dtype: str, shape,
                 meta: Optional[Dict] = None) -> np.ndarray:
    """Inverse of :func:`encode_shard` -> ndarray of ``dtype``/``shape``."""
    meta = meta or {}
    shape = tuple(shape)
    payload = np.asarray(payload, np.uint8)
    if enc == "raw":
        return payload.view(np.dtype(dtype)).reshape(shape)
    if enc == "zlib":
        rawb = zlib.decompress(payload.tobytes())
        return np.frombuffer(rawb, np.dtype(dtype)).reshape(shape).copy()
    if enc == "int8":
        import jax.numpy as jnp
        from repro.kernels.quant_blockwise.ops import dequantize_blockwise
        block = int(meta["block"])
        n_blocks = int(meta["n_blocks"])
        q = payload[:n_blocks * block].view(np.int8).reshape(n_blocks, block)
        s = payload[n_blocks * block:].view(np.float32)
        x = dequantize_blockwise(jnp.asarray(q), jnp.asarray(s), shape,
                                 block=block, dtype=jnp.float32)
        return np.asarray(x).astype(np.dtype(dtype))
    raise ValueError(f"unknown encoding {enc!r}")
