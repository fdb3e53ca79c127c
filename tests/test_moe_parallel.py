"""Multi-device MoE equivalence: the explicit-collective shard_map path must
match the dense (all-experts) oracle when capacity is not binding.

Runs in a subprocess with 8 forced host devices so the a2a/psum schedule is
really exercised (the main pytest process is pinned to 1 device)."""
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax, jax.numpy as jnp, numpy as np
    from repro.models import moe as moe_mod
    from repro.models.config import ModelConfig, MoEConfig
    from repro.parallel import sharding as shd

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    base = ModelConfig(name="t", d_model=32, vocab_size=64)
    key = jax.random.key(0)
    b, s, d = 4, 64, 32

    def params(cfg):
        from repro.models.params import ParamBuilder
        pb = ParamBuilder("init", key=jax.random.key(1))
        return moe_mod.moe_params(pb, cfg)

    # high capacity factor -> no token drops -> dense == shard_map exactly
    for n_exp, top_k, cf in [(8, 2, 8.0), (16, 4, 8.0)]:
        cfg_d = dataclasses.replace(base, moe=MoEConfig(
            n_experts=n_exp, top_k=top_k, d_ff_expert=64,
            capacity_factor=cf, impl="dense"))
        cfg_s = dataclasses.replace(cfg_d, moe=dataclasses.replace(
            cfg_d.moe, impl="shard_map"))
        p = params(cfg_d)
        x = jax.random.normal(jax.random.fold_in(key, n_exp), (b, s, d))

        y_dense, aux_d = moe_mod.moe_forward(p, x, cfg_d)

        with shd.use_sharding(mesh):
            y_sm, aux_s = jax.jit(
                lambda p_, x_: moe_mod.moe_forward(p_, x_, cfg_s))(p, x)

        err = float(jnp.max(jnp.abs(y_sm.astype(jnp.float32)
                                    - y_dense.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(y_dense.astype(jnp.float32))))
        assert err / scale < 5e-2, (n_exp, err, scale)  # bf16 compute
        assert abs(float(aux_s) - float(aux_d)) < 0.3, (float(aux_s), float(aux_d))
        # gradients flow through the a2a/psum schedule
        g = jax.jit(jax.grad(lambda p_, x_:
                             jnp.sum(moe_mod.moe_forward(p_, x_, cfg_s)[0]
                                     .astype(jnp.float32))))(p, x)
        gn = sum(float(jnp.sum(jnp.abs(l))) for l in jax.tree.leaves(g))
        assert np.isfinite(gn) and gn > 0
        print(f"E={n_exp} k={top_k}: rel_err={err/scale:.2e} OK")
    print("MOE_PARALLEL_OK")
""")


def test_shard_map_moe_matches_dense_on_8_devices():
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, timeout=420,
                       env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                            # pin cpu: an unpinned child hangs probing
                            # for accelerator platforms in this image
                            "JAX_PLATFORMS": "cpu"})
    assert "MOE_PARALLEL_OK" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])
