"""Pallas TPU kernels for the framework's compute hot-spots.

Each kernel package ships <name>.py (pl.pallas_call + explicit BlockSpec VMEM
tiling), ops.py (jit'd wrapper, interpret mode on the CPU test backend), and
ref.py (pure-jnp oracle used by the per-kernel shape/dtype sweeps in
tests/test_kernels.py).

  flash_attention   blocked online-softmax attention (FA-2 schedule, causal+GQA)
  ssd_scan          Mamba-2 chunked state-space-dual scan
  quant_blockwise   int8 blockwise quantisation (grad compression, int8 Adam)
"""
import jax


def auto_interpret() -> bool:
    """Interpret mode for a kernel call that did not choose one: on the CPU
    (the test backend) the kernel body runs in Python, on a TPU it compiles.
    Any other backend is an error, so a run on the wrong device never
    continues silently in the interpreter."""
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"Pallas TPU kernels cannot run on backend {backend!r}; "
                       "pass interpret=True to run them in the interpreter")
