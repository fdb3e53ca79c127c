"""Every cell of BENCHMARK.json resolves by name to its configuration,
traffic and metric files, and the command refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_bench_tiny as tiny
from chip import harness

ROOT = harness.ROOT
BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
WIDTHS = ("d_model", "n_heads", "n_kv_heads", "d_head", "d_ff",
          "vocab_size")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_to_its_files(cell):
    c = harness.resolve(cell)
    assert cell == f"{c.config['name']}.{cell.split('.', 1)[1]}"
    names = {m["name"] for m, _ in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m, read in c.end_to_end + c.per_layer:
        assert callable(read)
    for m, _ in c.per_layer:          # what it moves is reported here
        assert cell in E2E[m["moves"]].get("workloads", [cell])
    cfg, opt = harness.build_model(c.config)
    for k, v in c.config["model"].items():
        assert getattr(cfg, k) == v
    assert cfg.n_params() > 0 and opt.lr > 0


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_states_its_source_and_cuts(conf):
    path = ROOT / conf["file"]
    assert str(path.relative_to(ROOT)).startswith(BENCH["paths"][0] + "/")
    c = json.loads(path.read_text())
    assert c["name"] == conf["name"] and c["reduced"] == conf["reduced"]
    for k in WIDTHS:                  # no width is cut
        assert c["model"][k] == c["published"][k]
        assert k not in conf["reduced"]
    for k in ("loss_gap", "grad_norm_gap", "update_norm_gap"):
        assert 0 < c["limits"][k] < 1


def test_every_metric_has_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (harness.HERE / "metrics" / f"{m['name']}.py").is_file()
    for m in BENCH["per_layer"]:
        assert m["moves"] in E2E and m["workloads"]


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", CELLS[0],
         "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_exits_nonzero_without_a_tpu():
    p = _run(ROOT)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for rel in BENCH["paths"]:
        shutil.copytree(ROOT / rel, tmp_path / rel,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_tiny_copies_resolve():
    bench = tiny.tiny_bench()
    for w in bench["workloads"]:
        c = harness.resolve(w["name"], bench)
        assert harness.layout_chips(c.config) == c.chips


def test_tiny_copies_of_one_chip_cells_run_on_one_device():
    # the four-chip cell's tiny copy runs in a process with four devices
    # (test_chip_bench_x4.py); in this one it finds too few
    bench = tiny.tiny_bench()
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    assert chips["tiny.train"] == 1 and chips["tiny-x4.train"] == 4
    assert tiny.run_tiny("tiny.train")["device"]["count"] == 1
    with pytest.raises(harness.NoChip):
        tiny.run_tiny("tiny-x4.train")
