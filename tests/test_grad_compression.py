"""Cross-pod int8 gradient compression: numerical correctness on a real
multi-device pod axis (full-manual shard_map; subprocess forces 2 devices).
"""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, PartitionSpec as P
    from repro.train.trainer import _cross_pod_mean_int8

    mesh = jax.make_mesh((2,), ("pod",), axis_types=(AxisType.Auto,))
    g_local = jax.random.normal(jax.random.key(0), (2, 64, 128))  # per-pod grads

    def f(g):
        return _cross_pod_mean_int8({"w": g}, axis="pod")["w"]

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                                out_specs=P("pod"), check_vma=False))(g_local)
    # both pods must hold the same mean, within int8 quantisation error
    want = jnp.mean(g_local, axis=0)
    got0, got1 = np.asarray(out[0]), np.asarray(out[1])
    np.testing.assert_array_equal(got0, got1)
    amax = float(jnp.max(jnp.abs(g_local)))
    err = float(jnp.max(jnp.abs(got0 - np.asarray(want))))
    assert err <= amax / 127 * 1.01, (err, amax / 127)
    print("GRAD_COMPRESSION_OK", err)
""")


def test_cross_pod_int8_mean_on_2_devices():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=300,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            # pin cpu: an unpinned child hangs probing
                            # for accelerator platforms in this image
                            "JAX_PLATFORMS": "cpu"})
    assert "GRAD_COMPRESSION_OK" in r.stdout, (r.stdout[-1000:], r.stderr[-2000:])
