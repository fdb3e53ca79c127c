"""Single mode of ``repro.launch.train``: the in-process path that runs on
an accelerator (here at a tiny size on the CPU), and where the persistent
compilation cache lives."""
import jax
import pytest

from repro.data import SyntheticLMData
from repro.launch import train as lt
from repro.launch.compile_cache import CHECKOUT_CACHE_DIR, setup_compile_cache
from repro.launch.mesh import make_mesh

TINY = ["--arch", "olmo-1b", "--tiny", "--batch", "2", "--seq", "16",
        "--steps", "6", "--ckpt-every", "2", "--log-every", "1"]


@pytest.fixture
def no_cache_change(monkeypatch, tmp_path):
    # main() places the compile cache; with the variable set it sets nothing
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _setup(tmp_path, mesh=None):
    args = lt.build_argparser().parse_args(
        TINY + ["--ckpt-dir", str(tmp_path / "ckpt")])
    lt._apply_tiny(args)
    cfg, opt_cfg = lt.build_configs(args)
    plan = lt.plan_steps(cfg, opt_cfg, args.batch, args.seq, mesh=mesh)
    data = SyntheticLMData(cfg.vocab_size, args.seq, args.batch, args.seed)
    return args, cfg, opt_cfg, plan, data


def test_resume_continues_bit_exact(tmp_path):
    """Saved at step 4 through TCE, restored by a fresh engine from the
    store: steps 5-6 repeat the uninterrupted run's losses exactly."""
    args, cfg, opt_cfg, plan, data = _setup(tmp_path)
    state = plan.init(jax.random.key(args.seed))
    tce = lt.open_tce(args, lt.tree_nbytes(state))
    state, first = lt.train_span(plan, state, data, cfg, 0, 4, tce=tce,
                                 ckpt_every=4)
    state, live = lt.train_span(plan, state, data, cfg, 4, 6)
    assert tce.reconciler.quiesce(60)
    tce.close()

    tce = lt.open_tce(args, lt.tree_nbytes(state))
    step, host = lt.restore_state(tce, cfg, opt_cfg)
    tce.close()
    assert step == 4
    _, resumed = lt.train_span(plan, plan.place(host), data, cfg, 4, 6)
    assert [r[:2] for r in resumed] == [r[:2] for r in live]
    assert [r[0] for r in first + live] == [1, 2, 3, 4, 5, 6]


def test_sharded_plan_matches_single_device(tmp_path):
    """The mesh path (logical-axis shardings, activation constraints traced
    under the rules) gives the single-device losses on a 1x1 mesh."""
    mesh = make_mesh((1, 1), ("data", "model"))
    args, cfg, _, single, data = _setup(tmp_path)
    _, _, _, sharded, _ = _setup(tmp_path, mesh=mesh)
    key = jax.random.key(args.seed)
    _, a = lt.train_span(single, single.init(key), data, cfg, 0, 3)
    _, b = lt.train_span(sharded, sharded.init(key), data, cfg, 0, 3)
    assert [r[1] for r in a] == pytest.approx([r[1] for r in b], rel=1e-5)


def test_cli_trains_saves_and_resumes(tmp_path, capsys, no_cache_change):
    ckpt = ["--ckpt-dir", str(tmp_path / "ckpt")]
    assert lt.main(TINY + ckpt) == 0
    argv = [a if a != "6" else "8" for a in TINY] + ckpt + ["--resume"]
    assert lt.main(argv) == 0
    out = capsys.readouterr().out
    assert "resumed from step 6" in out
    assert "step     8 loss=" in out


def test_compile_cache_env_wins(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    setup_compile_cache()
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_checkout_path(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert setup_compile_cache() == str(CHECKOUT_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(CHECKOUT_CACHE_DIR)
        assert CHECKOUT_CACHE_DIR.parent.joinpath("chip_smoke.py").exists()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
