"""Sharding-rule legality properties + HLO parser sanity."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.parallel import sharding as shd


@pytest.fixture(scope="module")
def mesh():
    # single device, but axis sizes 1x1 exercise the code paths; divisibility
    # logic is tested against a fake mesh-shape dict below
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


LOGICAL = list(shd.DEFAULT_RULES.keys()) + [None, "unknown_axis"]


@st.composite
def axes_and_shape(draw):
    ndim = draw(st.integers(0, 5))
    axes = tuple(draw(st.sampled_from(LOGICAL)) for _ in range(ndim))
    shape = tuple(draw(st.sampled_from([1, 2, 3, 8, 16, 17, 64, 128, 256]))
                  for _ in range(ndim))
    return axes, shape


class _FakeMesh:
    def __init__(self, shape_map):
        self.axis_names = tuple(shape_map)
        import numpy as _np
        self.devices = _np.empty(tuple(shape_map.values()), object)


@given(aas=axes_and_shape(),
       mesh_shape=st.sampled_from([{"data": 16, "model": 16},
                                   {"pod": 2, "data": 16, "model": 16},
                                   {"data": 4, "model": 2}]),
       preset=st.sampled_from(sorted(shd.RULES_PRESETS)))
@settings(max_examples=150, deadline=None)
def test_spec_for_always_legal(aas, mesh_shape, preset):
    """Property: any (logical axes, shape, mesh, rules preset) yields a legal
    PartitionSpec: no mesh axis used twice, every used axis divides its dim."""
    axes, shape = aas
    ctx = shd.ShardingContext.__new__(shd.ShardingContext)
    ctx.mesh = _FakeMesh(mesh_shape)
    ctx.rules = dict(shd.RULES_PRESETS[preset])
    spec = shd.spec_for(axes, shape, ctx)
    used = []
    for dim, entry in enumerate(spec):
        for ax in ((entry,) if isinstance(entry, str) else (entry or ())):
            assert ax not in used, f"axis {ax} used twice in {spec}"
            used.append(ax)
    # divisibility
    for dim, entry in enumerate(list(spec)):
        total = 1
        for ax in ((entry,) if isinstance(entry, str) else (entry or ())):
            total *= mesh_shape[ax]
        assert shape[dim] % total == 0


def test_spec_for_first_wins_dedup():
    ctx = shd.ShardingContext.__new__(shd.ShardingContext)
    ctx.mesh = _FakeMesh({"pod": 2, "data": 16, "model": 16})
    ctx.rules = dict(shd.DEFAULT_RULES)
    # batch takes (pod, data); cache_seq would also want them -> dropped
    spec = shd.spec_for(("batch", "cache_seq", "act_kv_heads", None),
                        (128, 32768, 8, 128), ctx)
    assert spec[0] == ("pod", "data")
    assert len(spec) < 2 or spec[1] is None
    # with batch=1 the cache_seq dim picks them up instead
    spec = shd.spec_for(("batch", "cache_seq", "act_kv_heads", None),
                        (1, 32768, 8, 128), ctx)
    assert spec[1] == ("pod", "data")


def test_constrain_noop_without_context():
    x = jnp.ones((4, 4))
    y = shd.constrain(x, ("batch", "act_embed"))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(x))


# --------------------------------------------------------------------------- #
# HLO parser
# --------------------------------------------------------------------------- #
def test_hloparse_counts_scan_flops():
    """flops of scan(matmul x N) must be N * single-matmul flops."""
    from repro.launch import hloparse

    def f(x, w):
        def body(c, _):
            return c @ w, None
        out, _ = jax.lax.scan(body, x, None, length=8)
        return out

    x = jnp.zeros((64, 64))
    w = jnp.zeros((64, 64))
    hlo = jax.jit(f).lower(x, w).compile().as_text()
    st_ = hloparse.analyze(hlo)
    want = 8 * 2 * 64 ** 3
    assert st_.flops == pytest.approx(want, rel=0.05), (st_.flops, want)


def test_hloparse_collective_wire_factors():
    from repro.launch.hloparse import _wire_factor
    assert _wire_factor("all-gather", 16) == pytest.approx(15 / 16)
    assert _wire_factor("all-reduce", 16) == pytest.approx(2 * 15 / 16)
    assert _wire_factor("reduce-scatter", 16) == 15
    assert _wire_factor("collective-permute", 2) == 1.0
    assert _wire_factor("all-reduce", 1) == 0.0
