"""The plain reference against the program at a toy size on the CPU: the
same initial weights from the seed, and with the program computing in
float32 the same loss and gradients. Also the state fingerprint, which must
change with any one word of the state."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_bench_tiny as tiny
from chip import harness, reference

SEED = 2**31 + 5


@pytest.fixture(scope="module")
def tiny_config():
    return json.loads((tiny.HERE / "tiny.json").read_text())


def test_initial_weights_match_the_program(tiny_config):
    from repro.train import init_train_state
    cfg, opt = harness.build_model(tiny_config)
    prog = init_train_state(cfg, opt, jax.random.key(SEED)).params
    ref = reference.init_params(reference.Dims.of(tiny_config["model"]), SEED)
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_loss_and_gradients_match_a_float32_program(tiny_config):
    from repro.models.model import loss_fn
    model = dict(tiny_config["model"], compute_dtype="float32")
    cfg, _ = harness.build_model(dict(tiny_config, model=model))
    dims = reference.Dims.of(model)
    params = reference.init_params(dims, SEED)
    from chip.tokens import TokenStream
    b = TokenStream(cfg.vocab_size, tiny_config["seq"], tiny_config["batch"],
                    SEED, zipf_a=1.3, n_patterns=64, noise=0.15).rows([0])[0]
    with jax.default_matmul_precision("highest"):
        (lp, _), gp = jax.value_and_grad(
            lambda p: loss_fn(p, cfg, b), has_aux=True)(params)
    lr, gr = jax.value_and_grad(
        lambda p: reference.loss(p, b["tokens"], b["labels"], dims))(params)
    assert float(lr) == pytest.approx(float(lp), rel=1e-5)
    for a, c in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-3, atol=1e-6)


def test_fingerprint_sees_one_changed_word():
    rng = np.random.default_rng(3)
    tree = {"f": rng.standard_normal((3, 5, 7)).astype(np.float32),
            "i": np.arange(11, dtype=np.int32) - 4,
            "u": rng.integers(0, 2**32, (2,), dtype=np.uint32),
            "h": rng.standard_normal(9).astype(jnp.bfloat16)}
    fp = jax.jit(harness.fingerprint)
    before = fp(tree)
    assert harness.leaves_differ(before, fp(dict(tree))) == 0
    swapped = dict(tree, f=tree["f"][::-1].copy())
    tree["f"][1, 2, 3] += 1.0
    assert harness.leaves_differ(before, fp(tree)) == 1
    assert harness.leaves_differ(before, fp(swapped)) == 1


@pytest.mark.parametrize("given", [False, True], ids=["no_devices",
                                                    "one_device"])
def test_one_block_of_all_rows_on_one_device_is_the_plain_reference(
        tiny_config, given):
    from chip.tokens import TokenStream
    data = TokenStream(tiny_config["model"]["vocab_size"], tiny_config["seq"],
                       tiny_config["batch"], SEED, zipf_a=1.3, n_patterns=64,
                       noise=0.15)
    args = (tiny_config["model"], tiny_config["optimizer"], SEED,
            data.rows(range(3)))
    blocked = reference.train(*args, devices=jax.devices()[:given],
                              row_block=tiny_config["batch"])
    # 4 x 32 positions: dividing once at the end is exact, so bit for bit
    assert blocked == reference.train(*args)


def test_a_configuration_names_its_reference_module(tiny_config):
    from chip.tests import ref_probe
    config = dict(tiny_config, reference={"module": "tests.ref_probe",
                                          "flops": "tests.ref_probe"})
    mod, options, count = harness.reference_of(config)
    assert mod is ref_probe and options == {}
    assert harness.reference_of(tiny_config)[0] is reference
    cell = harness.resolve("tiny.train", tiny.tiny_bench())
    cell.config = config
    ref_probe.CALLS.clear()
    out = harness.run(cell, SEED, 0.2, False, 0.0, require_tpu=False)
    assert out["correct"], out["checks"]
    assert [c[0] for c in ref_probe.CALLS] == ["train", "flops"]
    assert ref_probe.CALLS[0][1]["devices"] == jax.devices()[:1]
