"""Pipeline parallelism: GPipe schedule over a 2-stage axis must equal
sequential layer execution (subprocess with 2 forced devices)."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp, numpy as np
    from repro.parallel.pipeline import pipeline

    mesh = jax.make_mesh((2,), ("pod",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    n_stages, layers_per_stage, d, b = 2, 3, 16, 8
    key = jax.random.key(0)
    W = jax.random.normal(key, (n_stages, layers_per_stage, d, d)) * 0.3
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, d))

    def layer_fn(w, h):
        return jnp.tanh(h @ w)

    # sequential reference
    ref = x
    for s in range(n_stages):
        for l in range(layers_per_stage):
            ref = layer_fn(W[s, l], ref)

    out = jax.jit(lambda W_, x_: pipeline(layer_fn, W_, x_, mesh=mesh,
                                          axis="pod", n_micro=4))(W, x)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-5, err
    print("PIPELINE_OK", err)
""")


def test_gpipe_matches_sequential_on_2_devices():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            # pin cpu: an unpinned child hangs probing
                            # for accelerator platforms in this image
                            "JAX_PLATFORMS": "cpu"})
    assert "PIPELINE_OK" in r.stdout, (r.stdout[-1000:], r.stderr[-2500:])
