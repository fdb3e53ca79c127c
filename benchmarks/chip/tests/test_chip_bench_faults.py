"""A run with the timed path broken underneath comes out not correct: the
harness's look for a chip is skipped, everything else of a run is driven
at a toy size on the CPU."""
import dataclasses

import jax
import numpy as np
import pytest

import chip_bench_tiny as tiny


def broken_plans(monkeypatch, fault):
    """Replace the step that ``plan_steps`` returns with a faulty one."""
    from repro.launch import train as lt
    from repro.train import TrainConfig, make_train_step
    real = lt.plan_steps

    def plan_steps(cfg, opt_cfg, batch, seq):
        plan = real(cfg, opt_cfg, batch, seq)
        core = make_train_step(cfg, opt_cfg, TrainConfig())
        if fault == "state_unchanged":
            step = jax.jit(lambda s, b: (s, core(s, b)[1]))
        elif fault == "half_batch":
            step = jax.jit(lambda s, b: core(
                s, {k: v[: v.shape[0] // 2] for k, v in b.items()}),
                donate_argnums=(0,))
        else:                                    # the loss altered
            def altered(s, b):
                s, m = core(s, b)
                return s, dict(m, loss=m["loss"] * 1.001)
            step = jax.jit(altered, donate_argnums=(0,))
        return dataclasses.replace(plan, step=step)

    monkeypatch.setattr(lt, "plan_steps", plan_steps)


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.resume"])
def test_sound_run_is_correct(cell):
    out = tiny.run_tiny(cell)
    assert out["correct"], out["checks"]
    # the compiled step needs at least the state it is given
    assert out["device"]["step_footprint_bytes"] > 0


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.resume"])
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "loss_altered"])
def test_broken_step_is_not_correct(monkeypatch, fault, cell):
    broken_plans(monkeypatch, fault)
    out = tiny.run_tiny(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["tiny.train_ckpt", "tiny.resume"])
def test_altered_checkpoint_is_not_correct(monkeypatch, cell):
    from repro.core.tce import engine
    real = engine.flatten_pytree

    def altered(tree):
        flat = real(tree)
        key = max(flat, key=lambda k: flat[k].size)
        leaf = np.array(flat[key])
        leaf.reshape(-1)[0] += 1
        flat[key] = leaf
        return flat

    monkeypatch.setattr(engine, "flatten_pytree", altered)
    out = tiny.run_tiny(cell, save_every=20)
    assert not out["correct"], out["checks"]
