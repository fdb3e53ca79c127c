"""The program's spans and counters (``repro.obs``): where each span lands
in a profiler trace of a tiny training step, a TCE save made durable and a
restore, what the counts in it add up to, and that with no profiler they
leave nothing."""
import collections
import glob

import jax
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro import obs
from repro.data import SyntheticLMData
from repro.launch import compile_cache
from repro.launch import train as lt

TINY = ["--arch", "olmo-1b", "--tiny", "--batch", "2", "--seq", "16",
        "--steps", "2"]
SAVE_SPANS = ("transom.save.wait", "transom.save.d2h",
              "transom.save.cache_write")
PERSIST_SPANS = ("transom.persist.digest", "transom.store.crc",
                 "transom.store.write")
RESTORE_SPANS = ("transom.store.read", "transom.store.crc",
                 "transom.restore.unshard")


def _run(tmp_path, trace_dir=None):
    """One step through ``train_span``, a save made durable, a restore by
    a fresh engine; harness-style ``bench.*`` spans around each call.
    Returns the state's bytes."""
    args = lt.build_argparser().parse_args(
        TINY + ["--ckpt-dir", str(tmp_path / "ckpt")])
    lt._apply_tiny(args)
    cfg, opt_cfg = lt.build_configs(args)
    plan = lt.plan_steps(cfg, opt_cfg, args.batch, args.seq)
    data = SyntheticLMData(cfg.vocab_size, args.seq, args.batch, args.seed)
    state = plan.init(jax.random.key(args.seed))
    nbytes = lt.tree_nbytes(state)
    lt.train_span(plan, state, data, cfg, 0, 1)      # compiled before
    state = plan.init(jax.random.key(args.seed))
    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    try:
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("bench.train_span"):
                state, _ = lt.train_span(plan, state, data, cfg, 0, 1)
            tce = lt.open_tce(args, nbytes)
            with TraceAnnotation("bench.save"):
                tce.save(1, state)
            assert tce.reconciler.quiesce(60)
            tce.close()
            tce = lt.open_tce(args, nbytes)
            with TraceAnnotation("bench.restore"):
                step, _ = lt.restore_state(tce, cfg, opt_cfg)
            tce.close()
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    assert step == 1
    return nbytes


def _events(trace_dir):
    """(name, start_ns, end_ns, thread, stats) of the bench.* and
    transom.* host events; threads numbered by line."""
    path, = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out, thread = [], 0
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            thread += 1
            for e in line.events:
                if e.name.startswith(("bench.", "transom.")):
                    out.append((e.name, e.start_ns, e.end_ns, thread,
                                dict(e.stats)))
    return out


def _in(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def _counted(evs):
    """Each counter's sum over the ``transom.count`` events."""
    out = collections.Counter()
    for e in evs:
        if e[0] == "transom.count":
            out[e[4]["counter"]] += e[4]["n"]
    return out


def _check_counts(nbytes, grew):
    # raw codec, first save: every leaf written once, each byte through
    # crc32 twice (the reconciler's digest, the store's payload crc)
    assert grew["tce.save.d2h_bytes"] == nbytes
    assert grew["tce.persist.bytes"] == nbytes
    assert grew["tce.persist.crc_bytes"] == 2 * nbytes
    assert grew["tce.restore.read_bytes"] == nbytes
    assert grew["tce.restore.crc_bytes"] == nbytes
    assert grew["tce.reconciler.cpu_s"] > 0


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("obs")
    nbytes = _run(tmp, str(tmp / "trace"))
    evs = _events(str(tmp / "trace"))
    return nbytes, _counted(evs), evs


def test_spans_nest_in_their_parents_on_the_profilers_clock(traced):
    _, _, evs = traced

    def named(name):
        return [e for e in evs if e[0] == name]

    win, = named("bench.window")
    main = win[3]
    assert all(_in(e, win) for e in evs if e is not win)

    step, = named("transom.step")
    assert _in(step, named("bench.train_span")[0]) and step[3] == main
    assert step[4]["step_num"] == 0

    save, = named("bench.save")
    parts = [named(n)[0] for n in SAVE_SPANS]
    assert all(_in(p, save) and p[3] == main for p in parts)
    assert [p[0] for p in sorted(parts, key=lambda p: p[1])] == [
        "transom.save.d2h", "transom.save.wait", "transom.save.cache_write"]

    persist, = named("transom.persist")
    assert persist[3] != main and persist[4] == {"step": 1, "rank": 0}
    assert save[2] <= persist[1]
    held = [e for e in evs if e[0] in PERSIST_SPANS and e[3] == persist[3]]
    assert held and all(_in(e, persist) for e in held)
    assert {e[0] for e in held} == set(PERSIST_SPANS)
    commit, = named("transom.persist.commit")
    assert commit[3] == persist[3] and persist[2] <= commit[1]

    restore_call, = named("bench.restore")
    restore, = named("transom.restore")      # the outermost call only
    assert _in(restore, restore_call) and restore[3] == main
    held = [e for e in evs if e[0] in RESTORE_SPANS and e[3] == main]
    assert {e[0] for e in held} == set(RESTORE_SPANS)
    assert all(_in(e, restore) for e in held)
    unflatten, = named("transom.restore.unflatten")
    assert _in(unflatten, restore_call) and restore[2] <= unflatten[1]


def test_counts_land_in_the_trace_where_they_are_made(traced):
    nbytes, grew, evs = traced
    _check_counts(nbytes, grew)
    counts = [e for e in evs if e[0] == "transom.count"]
    persist, = [e for e in evs if e[0] == "transom.persist"]

    def summed(counter, parent=None):
        return sum(e[4]["n"] for e in counts if e[4]["counter"] == counter
                   and (parent is None or _in(e, parent)))

    assert summed("tce.persist.bytes", persist) == nbytes
    assert summed("tce.persist.crc_bytes", persist) == 2 * nbytes
    restore, = [e for e in evs if e[0] == "transom.restore"]
    assert summed("tce.restore.read_bytes", restore) == nbytes
    assert summed("tce.restore.crc_bytes", restore) == nbytes


def test_counts_are_inert_with_no_profiler_running(tmp_path):
    """With no profiler the program runs through the same spans and
    counts, and nothing keeps them: a trace started later holds only the
    counts made inside it."""
    _run(tmp_path / "untraced")
    obs.count("test.before", 1)
    trace_dir = str(tmp_path / "trace")
    jax.profiler.start_trace(trace_dir)
    try:
        obs.count("test.inside", 2)
    finally:
        jax.profiler.stop_trace()
    assert _counted(_events(trace_dir)) == {"test.inside": 2}


@pytest.fixture
def counted(monkeypatch):
    """What ``repro.obs.count`` is given, summed per counter."""
    out = collections.Counter()
    monkeypatch.setattr(obs, "count",
                        lambda name, n: out.__setitem__(name, out[name] + n))
    return out


def test_compile_clock_counts_nested_events_once(counted):
    clock = compile_cache.CompileClock()
    trace = "/jax/core/compile/jaxpr_trace_duration"
    backend = "/jax/core/compile/backend_compile_duration"
    clock.enter(trace, 0.0)                    # the outer trace
    for a, b in [(1.0, 1.5), (2.0, 2.25)]:     # jitted calls inside it
        clock.enter(trace, a)
        clock(trace, a, b)
    clock(trace, 0.0, 3.0)
    clock.enter(backend, 3.0)
    clock(backend, 3.0, 5.0)
    other = "/jax/compilation_cache/cache_retrieval_time_sec"
    clock.enter(other, 3.0)                    # inside the backend compile
    clock(other, 3.0, 3.5)
    assert counted["compile.seconds"] == pytest.approx(5.0)


def test_compile_counters_follow_jax(monkeypatch, tmp_path, counted):
    # with the variable set, setup_compile_cache moves no cache directory
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    compile_cache.setup_compile_cache()
    compile_cache.setup_compile_cache()        # the listeners go on once
    jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(7.0)).block_until_ready()
    counted.clear()
    jax.jit(lambda x: x * 5 + 1)(jax.numpy.arange(7.0)).block_until_ready()
    assert counted["compile.seconds"] > 0
    misses = counted["compile.cache_misses"]
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    assert counted["compile.cache_hits"] == 1
    assert counted["compile.cache_misses"] == misses + 1


KEY_PROBE = r"""
import jax, jax.numpy as jnp
from repro import obs
from repro.launch import compile_cache

jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
seen = []
obs.count = lambda name, n: seen.append(name.split(".")[-1])
compile_cache.setup_compile_cache()


def step(x, scope):
    with jax.named_scope(scope):
        return jnp.sin(x) * 2


f = jax.jit(step, static_argnums=1)
x = jnp.ones(4)


def site_a(scope):
    f(x, scope).block_until_ready()


def site_b(scope):
    f(x, scope).block_until_ready()


for call, scope in [(site_a, "loss"), (site_b, "loss"), (site_a, "other")]:
    jax.clear_caches()
    del seen[:]
    call(scope)
    print([s for s in seen if s.startswith("cache_")][-1])
print('op_name="jit(step)/loss/sin"' in f.lower(x, "loss").compile().as_text())
"""


def test_the_compile_cache_keys_on_scopes_not_call_sites(tmp_path):
    """A step compiled from another call site is found in the persistent
    cache; one that differs only in a named scope, as the parent of a
    change that adds the scopes does, is compiled anew, so the profiler
    names each op's scope as this source sets it; and the compiled ops
    keep their whole scope path."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    src = Path(compile_cache.__file__).resolve().parents[2]
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(src),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    r = subprocess.run([sys.executable, "-c", KEY_PROBE], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.split() == ["cache_misses", "cache_hits", "cache_misses",
                                "True"]
