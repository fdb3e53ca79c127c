"""A run with the timed path broken underneath comes out not correct: the
harness's look for a chip is skipped, everything else of a run is driven
at a toy size on the CPU."""
import pytest

import chip_bench_tiny as tiny


def broken_plans(monkeypatch, fault):
    from repro.launch import train as lt
    monkeypatch.setattr(lt, "plan_steps", tiny.faulty_plan_steps(fault))


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.resume"])
def test_sound_run_is_correct(cell):
    out = tiny.run_tiny(cell)
    assert out["correct"], out["checks"]
    # the compiled step needs at least the state it is given
    assert out["device"]["step_footprint_bytes"] > 0


# the checks of the one-chip cells' tiny copies at one seed, as they read
# before configurations could name a layout and a reference
BEFORE = {"loss_gap": 6.040038841831574e-05,
          "grad_norm_gap": 0.0015033101077957657,
          "update_norm_gap": 0.0004163619506500744,
          "ckpt_leaves_differ": 0, "resume_loss_gap": 0.0,
          "resume_leaves_differ": 0}


@pytest.mark.parametrize("cell", ["tiny.train", "tiny.train_ckpt",
                                  "tiny.resume"])
def test_sound_run_reads_as_before(cell):
    # a save every 2 steps, so that the short window holds one on any CPU
    saves = {"save_every": 2} if cell == "tiny.train_ckpt" else {}
    out = tiny.run_tiny(cell, seed=2**31 + 1515, **saves)
    got = {k: c["value"] for k, c in out["checks"].items()}
    assert got == {k: BEFORE[k] for k in got}
    assert {"loss_gap", "grad_norm_gap", "update_norm_gap"} <= set(got)


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
    monkeypatch.setattr(engine, "flatten_pytree",
                        tiny.altered_saves(engine.flatten_pytree))
    out = tiny.run_tiny(cell, save_every=20)
    assert not out["correct"], out["checks"]
