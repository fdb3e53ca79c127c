"""Every cell of BENCHMARK.json resolves by name to its configuration,
traffic and metric files, and the command refuses to run without a TPU.
What a configuration may cut is one rule (``harness.check_cuts``), and
nested blocks of a configuration merge into the registry's."""
import copy
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
CONFS = {c["name"]: c for c in BENCH["configs"]}


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
    assert_runs_as_stated(cfg, c.config["model"])
    assert cfg.n_params() > 0 and opt.lr > 0


def assert_runs_as_stated(cfg, model):
    for k, v in model.items():        # a nested block field by field
        got = getattr(cfg, k)
        for f, x in (v.items() if isinstance(v, dict) else [(None, v)]):
            assert (got if f is None else getattr(got, f)) == x, (k, f)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_states_its_source_and_cuts(conf):
    path = ROOT / conf["file"]
    assert str(path.relative_to(ROOT)).startswith(BENCH["paths"][0] + "/")
    c = json.loads(path.read_text())
    assert {"d_model", "d_head", "d_ff", "n_heads", "n_kv_heads",
            "vocab_size"} <= set(c["published"])
    harness.check_cuts(conf, c)       # no width cut, every cut listed
    for k in ("loss_gap", "grad_norm_gap", "update_norm_gap"):
        assert 0 < c["limits"][k] < 1


# --------------------------------------------------------------------------- #
# What a configuration may cut (test-only configurations)
# --------------------------------------------------------------------------- #
def olmo_file(name="olmo1b-6l"):
    return json.loads((ROOT / CONFS[name]["file"]).read_text())


def hybrid_file():
    """A test-only hybrid configuration at toy sizes with nested ``ssm``,
    ``hybrid`` and ``moe`` blocks, published as it runs: ``model`` states
    some fields of each block (the registry's give the rest), ``published``
    every width."""
    model = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
             "d_head": 16, "d_ff": 128, "vocab_size": 256,
             "ssm": {"d_state": 16, "head_dim": 16, "chunk": 32},
             "hybrid": {"attn_period": 2, "attn_offset": 1},
             "moe": {"n_experts": 16, "d_ff_expert": 64, "top_k": 2,
                     "impl": "dense"}}
    published = copy.deepcopy(model)
    published["ssm"].update(d_conv=4, expand=2, n_groups=1)
    published["moe"].update(n_shared=0, d_ff_shared=0, first_k_dense=0,
                            every=2, offset=1, capacity_factor=1.25,
                            aux_loss_weight=0.01)
    return {"name": "hybrid-test", "arch": "jamba-v0.1-52b",
            "published": published, "reduced": [], "model": model,
            "optimizer": {"lr": 4e-4, "warmup_steps": 2}, "batch": 2,
            "seq": 32}


def share(c, key, held, chips):
    """``c`` holding ``held`` of its published ``key`` over ``chips``."""
    block, _, field = key.rpartition(".")
    (c["model"][block] if block else c["model"])[field] = held
    c["reduced"].append(key)
    c.setdefault("share", {})[key] = {
        "chips": chips, "how": f"{chips} chips each hold {held}"}
    return c


def unpublish(c, key):
    block, _, field = key.rpartition(".")
    del c["published"][block][field]
    return c


def publish(c, key, value):
    block, _, field = key.rpartition(".")
    c["published"][block][field] = value
    return c


def heads_share(c, chips):
    c = share(c, "n_heads", c["published"]["n_heads"] // chips, chips)
    c["model"]["n_kv_heads"] = c["published"]["n_kv_heads"] // chips
    c["reduced"].append("n_kv_heads")
    return c


def cut(c, key, value, listed=None):
    block, _, field = key.rpartition(".")
    (c["model"][block] if block else c["model"])[field] = value
    if listed:
        c["reduced"].append(listed)
    return c


ACCEPTED = {
    "olmo1b-6l": lambda: olmo_file("olmo1b-6l"),
    "olmo1b-16l-x4": lambda: olmo_file("olmo1b-16l-x4"),
    "vocab_eighth": lambda: share(olmo_file(), "vocab_size", 50304 // 8, 8),
    "heads_half": lambda: heads_share(hybrid_file(), 2),
    "experts_8_of_16": lambda: share(hybrid_file(), "moe.n_experts", 8, 2),
    "hybrid_as_published": hybrid_file,
}

REFUSED = {                           # case: (config, why it is refused)
    "d_ff_cut": (lambda: cut(olmo_file(), "d_ff", 4096, "d_ff"),
                 "reduced names d_ff"),
    "d_ff_cut_unlisted": (lambda: cut(olmo_file(), "d_ff", 4096),
                          "width d_ff"),
    "ssm_d_state_cut": (lambda: cut(hybrid_file(), "ssm.d_state", 8,
                                    "ssm.d_state"),
                        "reduced names ssm.d_state"),
    "ssm_block_listed": (lambda: cut(hybrid_file(), "ssm.d_state", 8,
                                     "ssm"),
                         "reduced names ssm,"),
    "ssm_d_state_cut_unlisted": (lambda: cut(hybrid_file(), "ssm.d_state",
                                             8),
                                 "width ssm.d_state"),
    "ssm_width_cut_unpublished": (lambda: unpublish(cut(
        hybrid_file(), "ssm.d_state", 8), "ssm.d_state"),
        "width ssm.d_state runs at 8 and published does not state it"),
    "ssm_registry_width_unpublished": (lambda: unpublish(
        hybrid_file(), "ssm.expand"),
        "width ssm.expand runs at 2 and published does not state it"),
    "ssm_registry_width_differs": (lambda: publish(
        hybrid_file(), "ssm.expand", 4), "width ssm.expand is 2"),
    "moe_expert_width_cut": (lambda: cut(hybrid_file(), "moe.d_ff_expert",
                                         32, "moe.d_ff_expert"),
                             "reduced names moe.d_ff_expert"),
    "vocab_share_not_in_reduced": (lambda: dict(
        share(olmo_file(), "vocab_size", 50304 // 8, 8),
        reduced=["n_layers", "batch"]), "reduced does not list it"),
    "vocab_cut_with_no_share": (lambda: cut(olmo_file(), "vocab_size",
                                            50304 // 8, "vocab_size"),
                                "no share entry"),
    "vocab_under_an_eighth": (lambda: share(olmo_file(), "vocab_size",
                                            50304 // 16, 16),
                              "under an eighth"),
    "held_times_chips_misses": (lambda: share(olmo_file(), "vocab_size",
                                              6000, 8),
                                "must equal the published"),
    "experts_under_8": (lambda: share(hybrid_file(), "moe.n_experts", 4, 4),
                        "under 8"),
    "kv_heads_not_divided": (lambda: share(hybrid_file(), "n_heads", 2, 2),
                             "key-value heads"),
    "unlisted_depth": (lambda: dict(olmo_file(), reduced=["batch"]),
                       "n_layers is 6"),
}


def entry(c):
    return {"name": c["name"], "reduced": list(c["reduced"])}


@pytest.mark.parametrize("case", ACCEPTED)
def test_check_cuts_accepts(case):
    c = ACCEPTED[case]()
    harness.check_cuts(entry(c), c)


@pytest.mark.parametrize("case", REFUSED)
def test_check_cuts_refuses(case):
    make, why = REFUSED[case]
    c = make()
    with pytest.raises(ValueError, match=why):
        harness.check_cuts(entry(c), c)


def test_check_cuts_runs_where_a_cell_resolves(tmp_path):
    bad = cut(olmo_file(), "d_ff", 4096, "d_ff")
    (tmp_path / "bad.json").write_text(json.dumps(bad))
    bench = copy.deepcopy(BENCH)
    conf = next(c for c in bench["configs"] if c["name"] == bad["name"])
    conf.update(file=str(tmp_path / "bad.json"), reduced=bad["reduced"])
    with pytest.raises(ValueError, match="d_ff"):
        harness.resolve(CELLS[0], bench)


# --------------------------------------------------------------------------- #
# Nested blocks through build_model
# --------------------------------------------------------------------------- #
def test_nested_blocks_merge_into_the_registry_config():
    import jax

    from repro.configs import get_config
    from repro.launch import train as lt
    c = hybrid_file()
    cfg, opt = harness.build_model(c)
    assert_runs_as_stated(cfg, c["model"])
    base = get_config("jamba-v0.1-52b")
    # fields not stated keep the registry's values
    assert cfg.ssm.d_conv == base.ssm.d_conv
    assert cfg.ssm.expand == base.ssm.expand
    assert cfg.moe.every == base.moe.every
    assert [cfg.layer_kind(i) for i in range(2)] == ["ssm", "attn"]
    state = lt.plan_steps(cfg, opt, c["batch"], c["seq"]).init(
        jax.random.key(2**31 + 17))
    mix = state.params["segments"]["stack"]["l0"]["mix"]
    d_in = cfg.ssm.expand * cfg.d_model
    assert mix["conv_w"].shape[-1] == d_in + 2 * cfg.ssm.n_groups * 16
    assert state.params["segments"]["stack"]["l1"]["mlp"]["wi"].shape[1] \
        == 16


@pytest.mark.parametrize("arch,model", [
    ("jamba-v0.1-52b", {"ssm": {"d_stat": 16}}),
    ("jamba-v0.1-52b", {"hybrid": {"attn_period": 2, "period": 2}}),
    ("olmo-1b", {"ssm": {"d_state": 16}}),
    ("jamba-v0.1-52b", {"mla": {"kv_lora_rank": 16}}),
], ids=["unknown_ssm_field", "unknown_hybrid_field", "no_ssm_block",
        "no_mla_block"])
def test_build_model_refuses_what_the_registry_lacks(arch, model):
    with pytest.raises(ValueError):
        harness.build_model({"arch": arch, "model": model,
                             "optimizer": {}})


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
