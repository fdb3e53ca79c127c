"""The four-chip path at a toy size on four virtual CPU devices, in a
process of its own (``x4_probe.py``): a whole run of the tiny copy of the
four-chip cell through ``harness.run`` passes its checks with the state
split over the devices, and comes out not correct with its step broken
underneath (the exchange between chips left out among the faults); with
TCE saves it reads its sharded save back exactly, reads the save and
persist metrics traced, and comes out not correct where the save is
altered; and the reference spread over the devices in blocks of rows
gives what the plain one gives, to float32 rounding."""
import json
import os
import subprocess
import sys

import pytest

import chip_bench_tiny as tiny
from chip import harness

PROBE = tiny.HERE / "x4_probe.py"


@pytest.fixture(scope="module")
def probe():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, str(PROBE)], cwd=tiny.CHIP.parents[1],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_run_on_a_2x2_mesh_passes_its_checks(probe):
    run = probe["run"]
    assert probe["devices"] == 4
    assert run["correct"], run["checks"]
    assert set(run["checks"]) == {"loss_gap", "grad_norm_gap",
                                  "update_norm_gap"}
    assert run["device"]["count"] == 4
    assert run["device"]["layout"] == {"data": 2, "model": 2}


def test_run_splits_the_state_over_the_devices(probe):
    state = probe["run"]["state"]
    assert len(state["per_device"]) == 4
    # every leaf with a parameter is split four ways; the step count is not
    assert max(state["per_device"]) <= 0.26 * state["total"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "loss_altered", "no_exchange"])
def test_broken_step_on_a_2x2_mesh_is_not_correct(probe, fault):
    assert probe["faults"][fault] is False


def test_sharded_save_reads_back_exactly(probe):
    run = probe["saves"]["sound"]
    assert run["count"] == 4
    assert run["correct"], run["checks"]
    assert run["checks"]["ckpt_leaves_differ"] == {"value": 0, "limit": 0}
    assert {"loss_gap", "grad_norm_gap", "update_norm_gap"} \
        <= set(run["checks"])


def test_traced_sharded_save_reads_its_per_layer_metrics(probe):
    # every per-layer metric of the cell that saves, but those that need
    # the chip: the device trace's (the CPU has no device plane) and mfu (a
    # chip's peak)
    cell = "olmo1b-6l.train_ckpt"
    want = {m["name"] for m in harness.load_benchmark()["per_layer"]
            if cell in m["workloads"] and m["source"] != "device_trace"}
    run = probe["saves"]["traced"]
    assert run["correct"]
    assert want - {"mfu"} <= set(run["read"])


def test_altered_sharded_save_is_not_correct(probe):
    run = probe["saves"]["altered"]
    assert not run["correct"]
    assert run["checks"]["ckpt_leaves_differ"]["value"] > 0


@pytest.mark.parametrize("plain,spread", [("plain", "spread"),
                                          ("half", "half_spread")])
def test_spread_reference_in_row_blocks_matches_the_plain_one(probe, plain,
                                                             spread):
    a, b = probe["reference"][plain], probe["reference"][spread]
    assert b["losses"] == pytest.approx(a["losses"], rel=1e-6)
    for key in ("grad_norms", "change_norms"):
        assert set(a[key]) == set(b[key])
        for k in a[key]:
            assert b[key][k] == pytest.approx(a[key][k], rel=1e-5), (key, k)


def test_reference_places_each_leaf_along_its_largest_divided_axis(probe):
    # tiny widths: d 64, heads x d_head 64, d_ff 128, vocab 256, 2 layers
    want = {"mix/wq": "(None, 'r', None)", "mix/wk": "(None, 'r', None)",
            "mix/wv": "(None, 'r', None)", "mix/wo": "(None, 'r', None)",
            "mlp/wi": "(None, None, 'r')", "mlp/wg": "(None, None, 'r')",
            "mlp/wo": "(None, 'r', None)"}
    got = probe["reference"]["placement"]
    for leaf, spec in want.items():
        assert got[f"segments/stack/l0/{leaf}"] == f"PartitionSpec{spec}"
    assert got["tok/table"] == "PartitionSpec('r', None)"
