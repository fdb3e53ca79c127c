"""The readers of the program's own spans and counts (``program.py`` and
the metrics that use it): on hand-made events, on a small trace recorded on
the CPU (``cpu_program_trace.xplane.pb``, made by ``record`` below) and on
a fresh recording; where the program has no spans they read nothing. The
harness's own reduction of the older recorded trace is pinned as it was."""
import dataclasses
import re
import shutil
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

CHIP = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(CHIP.parents[1] / "src"))
sys.path.insert(0, str(CHIP.parent))

from chip import harness, program, tracing  # noqa: E402
from chip.program import Program, Span  # noqa: E402

HERE = CHIP / "tests"
CPU_TRACE = HERE / "cpu_trace.xplane.pb"
PROGRAM_TRACE = HERE / "cpu_program_trace.xplane.pb"
CPU = dict(plane=re.compile(r"^/host:CPU$"), op_line="tf_XLAPjRtCpuClient")
CKPT = ("save_d2h_s", "save_wait_s", "persist_crc_s", "persist_io_s",
        "persist_crc_ratio", "reconciler_cpu_share",
        "persist_overlap_idle_s", "backward_ms", "optimizer_ms",
        "save_d2h_bandwidth")
DEVICE = ("persist_overlap_idle_s", "backward_ms", "optimizer_ms")
RESUME = ("restore_io_s", "restore_crc_s", "restore_copy_s", "compile_s",
          "compile_cache_hits", "compile_cache_misses",
          "restore_io_bandwidth", "restore_crc_bandwidth")
NEW = CKPT + RESUME
TABLE = ("transom.step", "transom.save.wait", "transom.save.d2h",
         "transom.save.cache_write", "transom.persist",
         "transom.persist.digest", "transom.store.crc", "transom.store.write",
         "transom.persist.commit", "transom.restore", "transom.store.read",
         "transom.restore.unshard", "transom.restore.unflatten")


def read(name, run):
    return harness.load_reader(name)(run)


# --------------------------------------------------------------------------- #
# Hand-made
# --------------------------------------------------------------------------- #
def hand_made():
    """A 20 s window on one device: steps 0-4 and 6-20 (s), a save 4-6
    (wait 0.5, d2h 1, cache write 0.5) whose persist runs on thread 2 at
    8-14 (digest 2, two leaves each crc 0.5 and written in 1), committed
    at 14; a restore at 15-19 (two reads of 1, two crcs of 0.5, unshard 1)
    and its unflatten at 19-19.5; 3 s of compile, 3 cache hits and 6
    misses counted; 2 GB copied from the device, 2 GB read and verified.
    The ops are the forward, the backward and the optimizer, in 30
    steps."""
    tr = tracing.Trace()
    tr.devices["/device:TPU:0"] = [("fusion.1", 0.0, 3.0),
                                   ("fusion.2", 6.0, 9.0),
                                   ("fusion.3", 12.0, 20.0)]
    tr.host = [("bench.window", 0.0, 20.0), ("bench.train_span", 0.0, 6.0),
               ("bench.save", 4.0, 6.0), ("bench.train_span", 6.0, 20.0)]

    def s(name, a, b, thread=1, **meta):
        return Span(name, a, b, thread, meta)

    def c(counter, n, t, thread=2):
        return Span("transom.count", t, t, thread,
                    {"counter": counter, "n": n})

    prog = Program(window=(0.0, 20.0), ops={"/device:TPU:0": [
        ("jit(core)/jvp(loss)/dot_general:", 0.0, 3.0),
        ("jit(core)/transpose(jvp(loss))/dot_general:", 6.0, 9.0),
        ("jit(core)/optimizer/mul:", 12.0, 20.0)]}, spans=[
        s("transom.save.wait", 4.0, 4.5), s("transom.save.d2h", 4.5, 5.5),
        s("transom.save.cache_write", 5.5, 6.0),
        s("transom.persist", 8.0, 14.0, 2, step=40, rank=0),
        s("transom.persist.digest", 8.0, 10.0, 2),
        s("transom.store.write", 10.0, 11.0, 2),
        s("transom.store.crc", 11.0, 11.5, 2),
        s("transom.store.write", 11.5, 12.5, 2),
        s("transom.store.crc", 12.5, 13.0, 2),
        s("transom.persist.commit", 14.0, 14.1, 2, step=40),
        # the main thread's crc is not the persist's
        s("transom.restore", 15.0, 19.0),
        s("transom.store.read", 15.0, 16.0), s("transom.store.crc", 16.0,
                                               16.5),
        s("transom.store.read", 16.5, 17.5), s("transom.store.crc", 17.5,
                                               18.0),
        s("transom.restore.unshard", 18.0, 19.0),
        s("transom.restore.unflatten", 19.0, 19.5)],
        counts=[c("tce.persist.crc_bytes", 100, 9.0),
                c("tce.persist.bytes", 50, 10.5),
                c("tce.persist.crc_bytes", 50, 11.2),
                c("tce.persist.bytes", 50, 12.0),
                c("tce.persist.crc_bytes", 50, 12.8),
                c("tce.reconciler.cpu_s", 4.0, 14.2),
                c("compile.seconds", 1.0, 15.0, 1),
                c("compile.seconds", 2.0, 19.9, 1),
                c("compile.cache_hits", 3, 19.9, 1),
                c("compile.cache_misses", 6, 19.9, 1),
                c("tce.save.d2h_bytes", 2e9, 5.5, 1),
                c("tce.restore.read_bytes", 1e9, 16.0, 1),
                c("tce.restore.crc_bytes", 1e9, 16.5, 1),
                c("tce.restore.read_bytes", 1e9, 17.5, 1),
                c("tce.restore.crc_bytes", 1e9, 18.0, 1)])
    return {"trace": tr, "program_trace": prog, "window_s": 20.0,
            "saves": [{}, {}], "resumes": [{}, {}, {}], "steps": [{}] * 30}


def test_readers_on_hand_made_events():
    run = hand_made()
    got = {name: read(name, run) for name in NEW}
    assert got["save_wait_s"] == pytest.approx(0.5)
    assert got["save_d2h_s"] == pytest.approx(1.0)
    assert got["persist_crc_s"] == pytest.approx(3.0)       # 2 + 0.5 + 0.5
    assert got["persist_io_s"] == pytest.approx(2.0)
    assert got["persist_crc_ratio"] == pytest.approx(2.0)   # 200 / 100
    # 4 s of CPU per commit, two saves in the window, over 20 s
    assert got["reconciler_cpu_share"] == pytest.approx(40.0)
    # steps 0-4, 6-20; persist 8-14; busy 8-9 and 12-14: idle 9-12
    assert got["persist_overlap_idle_s"] == pytest.approx(3.0)
    # one restore: reads 2 s, crcs 1 s, unshard + unflatten 1.5 s
    assert got["restore_io_s"] == pytest.approx(2.0)
    assert got["restore_crc_s"] == pytest.approx(1.0)
    assert got["restore_copy_s"] == pytest.approx(1.5)
    assert got["compile_s"] == pytest.approx(1.0)           # 3 s, 3 resumes
    assert got["compile_cache_hits"] == pytest.approx(1.0)
    assert got["compile_cache_misses"] == pytest.approx(2.0)
    assert got["save_d2h_bandwidth"] == pytest.approx(2.0)  # 2 GB in 1 s
    assert got["restore_io_bandwidth"] == pytest.approx(1.0)
    assert got["restore_crc_bandwidth"] == pytest.approx(2.0)
    # inside the steps: the backward's 3 s and the optimizer's 8 s
    assert got["backward_ms"] == pytest.approx(3e3 / 30)
    assert got["optimizer_ms"] == pytest.approx(8e3 / 30)


def test_readers_read_nothing_without_the_programs_events():
    run = hand_made()
    run["program_trace"] = None               # a program without repro.obs
    assert {name: read(name, run) for name in NEW} == dict.fromkeys(NEW)
    run = hand_made()
    run["program_trace"].spans = [s for s in run["program_trace"].spans
                                  if s.name != "transom.persist"]
    # no persist held in the trace: nothing per save
    for name in ("persist_crc_s", "persist_io_s", "persist_crc_ratio",
                 "persist_overlap_idle_s"):
        assert read(name, run) is None
    run["program_trace"].ops = {
        "/device:TPU:0": [("jit(core)/transpose(jvp(loss))/x", 6.0, 9.0)]}
    assert read("optimizer_ms", run) is None      # no optimizer scope
    run["program_trace"].spans = []
    # no restore in the trace: no bandwidth of its reads
    assert read("restore_io_bandwidth", run) is None
    run = hand_made()
    run["trace"] = None
    del run["program_trace"]
    assert {name: read(name, run) for name in NEW} == dict.fromkeys(NEW)


def _space(ops, events=()):
    """An ``XSpace`` with a TPU and a CPU plane, each holding ``ops``:
    (metadata key, op name, tf_op as a string, or as the key of a stat
    metadata named by it), and on its ``XLA Ops`` line ``events``:
    (metadata key, start ns, duration ns)."""
    space = program._xspace()()
    for name in ("/device:TPU:0", "/host:CPU"):
        plane = space.planes.add(name=name)
        for key, stat in [(1, "tf_op"), (2, "flops"),
                          (3, "jit(core)/optimizer/mul:")]:
            plane.stat_metadata.add(key=key).value.name = stat
        for key, op_name, tf_op in ops:
            op = plane.event_metadata.add(key=key).value
            op.name = op_name
            op.stats.add(metadata_id=2, str_value="16")
            if isinstance(tf_op, int):
                op.stats.add(metadata_id=1, ref_value=tf_op)
            elif tf_op is not None:
                op.stats.add(metadata_id=1, str_value=tf_op)
        line = plane.lines.add(name="XLA Ops", timestamp_ns=1000)
        for key, start, dur in events:
            line.events.add(metadata_id=key, offset_ps=start * 1000,
                            duration_ps=dur * 1000)
    return space


def test_op_scopes_read_each_ops_tf_op(tmp_path):
    """The scope path sits in the stats of an event's metadata, as a
    string or as a reference to a stat's name; an op without one keeps
    its place with an empty path, and only device planes count. Times are
    whole ns, as ``tracing.load`` has them."""
    path = tmp_path / "x.xplane.pb"
    path.write_bytes(_space(
        [(7, "%fusion.2 = f32[8] fusion(%p)",
          "jit(core)/transpose(jvp(loss))/dot:"),
         (8, "%fusion.3 = f32[8] fusion(%q)", 3),
         (9, "%copy.1 = f32[8] copy(%r)", None)],
        [(8, 500, 100), (7, 0, 400), (9, 700, 50)]).SerializeToString())
    got = program.op_events(str(path))
    assert list(got) == ["/device:TPU:0"]
    assert [e[0] for e in got["/device:TPU:0"]] == [
        "jit(core)/transpose(jvp(loss))/dot:", "jit(core)/optimizer/mul:", ""]
    assert [t for e in got["/device:TPU:0"] for t in e[1:]] == pytest.approx(
        [1000e-9, 1400e-9, 1500e-9, 1600e-9, 1700e-9, 1750e-9], rel=1e-12)


def test_ops_of_two_programs_that_share_a_name_keep_their_own_scopes(
        tmp_path):
    """HLO names are unique only inside a program: 'fusion.1' of the step
    and of another jitted call (a fingerprint at a save) carry their own
    metadata, and each op event is credited to its own scope path."""
    path = tmp_path / "x.xplane.pb"
    s = 1_000_000_000                                   # 1 s in ns
    path.write_bytes(_space(
        [(1, "fusion.1", "jit(core)/transpose(jvp(loss))/dot:"),
         (2, "fusion.1", "jit(fingerprint)/reduce_sum:"),
         (3, "fusion.2", "jit(core)/optimizer/mul:")],
        [(1, -1000, 3 * s), (2, 4 * s + 5 * s // 10 - 1000, s // 2),
         (3, 6 * s - 1000, 2 * s)]).SerializeToString())
    run = hand_made()
    prog = run["program_trace"]
    prog.ops, prog.path = None, str(path)
    assert read("backward_ms", run) == pytest.approx(3e3 / 30)
    assert read("optimizer_ms", run) == pytest.approx(2e3 / 30)


def test_a_step_without_its_named_scopes_raises():
    """The program left its spans, but the step's ops carry no 'loss'
    scope: its executable came from a compile without the named scopes
    (say, from a cache another source filled). That is a fault, not a
    metric that reads nothing."""
    run = hand_made()
    run["program_trace"].ops = {"/device:TPU:0": [
        ("jit(core)/jvp(f)/dot_general:", 0.0, 3.0),
        ("jit(core)/transpose(jvp(f))/dot_general:", 6.0, 9.0),
        ("jit(core)/mul:", 12.0, 20.0)]}
    for name in ("backward_ms", "optimizer_ms"):
        with pytest.raises(RuntimeError, match="'loss' scope"):
            read(name, run)
    run["program_trace"] = None               # a program without repro.obs
    assert read("backward_ms", run) is None


# --------------------------------------------------------------------------- #
# Recorded on the CPU
# --------------------------------------------------------------------------- #
def record(log_dir: Path) -> str:
    """The program at a toy size under the profiler, with the harness's
    own wrappers around its calls: two steps through ``train_span`` with a
    save after the second (``TimedSaves``), the save made durable, then
    ``resume_once`` (kill, open, restore from the store, place, one step).
    The step is a trivial jitted function, so the trace stays small.
    Returns the ``.xplane.pb`` path."""
    import jax
    from jax.profiler import TraceAnnotation as TA

    from chip.tokens import TokenStream
    from repro.launch import train as lt
    from repro.launch.compile_cache import setup_compile_cache

    setup_compile_cache()
    config = harness.json.loads((HERE / "tiny.json").read_text())
    cfg, opt_cfg = harness.build_model(config)
    plan = lt.plan_steps(cfg, opt_cfg, 2, 8)

    def step(state, batch):
        m = {k: batch["tokens"].mean() for k in ("loss", "grad_norm", "lr")}
        return state._replace(step=state.step + 1), m

    plan = dataclasses.replace(plan, step=jax.jit(step))
    traffic = harness.json.loads((CHIP / "traffic" / "train.json")
                                 .read_text())
    data = TokenStream.from_traffic(traffic, cfg.vocab_size, 8, 2, 5)
    state = plan.init(jax.random.key(5))
    nbytes = lt.tree_nbytes(state)
    args = SimpleNamespace(codec="raw", ckpt_dir=str(log_dir / "store"))
    state, _ = lt.train_span(plan, state, data, cfg, 0, 1)
    tce = harness.TimedSaves(lt.open_tce(args, nbytes), lambda s: [])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False             # keeps the file small
    jax.profiler.start_trace(str(log_dir / "trace"), profiler_options=opts)
    try:
        with TA("bench.window"):
            for i in (1, 2):
                with TA("bench.train_span"):
                    state, _ = lt.train_span(plan, state, data, cfg, i, i + 1,
                                             tce=tce, ckpt_every=3)
            assert tce.wait_commits(60)
            state, tce, _ = harness.resume_once(
                lt, plan, cfg, opt_cfg, data, state, tce, args, nbytes,
                lambda s: [])
    finally:
        jax.profiler.stop_trace()
        tce.close()
    return tracing.xplane_path(str(log_dir / "trace"))


def recorded_run(path) -> dict:
    """The run record the readers take, rebuilt from the trace alone."""
    tr = tracing.load(str(path), **CPU)
    win = tr.span("bench.window")
    return {"trace": tr, "program_trace": program.load(str(path)),
            "window_s": win[1] - win[0], "saves": tr.spans("bench.save"),
            "resumes": tr.spans("bench.restore"),
            "steps": tr.spans("bench.train_span")}


def check_recorded(run):
    prog = run["program_trace"]
    assert prog.window == run["trace"].span("bench.window")
    assert {s.name for s in prog.spans} == set(TABLE)
    got = {name: read(name, run) for name in NEW}
    # nothing runs on a TPU plane here: the device readers read nothing
    assert [got.pop(name) for name in DEVICE] == [None] * len(DEVICE)
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["persist_crc_ratio"] == 2.0        # raw codec, first save
    assert got["compile_s"] > 0                   # the step after a kill
    for name, span in [("save_d2h_s", "transom.save.d2h"),
                       ("save_wait_s", "transom.save.wait")]:
        assert got[name] == prog.named(span)[0].seconds
    restore, = prog.named("transom.restore")
    call, = run["trace"].spans("bench.restore")
    parts = ["transom.store.read", "transom.store.crc",
             "transom.restore.unshard"]
    assert got["restore_io_s"] + got["restore_crc_s"] == pytest.approx(
        sum(prog.seconds(n, [restore]) for n in parts[:2]))
    assert restore.seconds <= call[1] - call[0]


def test_readers_on_the_recorded_trace():
    check_recorded(recorded_run(PROGRAM_TRACE))


def test_readers_on_a_fresh_recording(tmp_path):
    check_recorded(recorded_run(record(tmp_path)))


def test_the_run_finds_its_own_trace(tmp_path, monkeypatch):
    """``of`` takes the profile under the temp directory whose window is
    the run's, and leaves another run's alone."""
    monkeypatch.setattr(program.tempfile, "tempdir", str(tmp_path))
    for name, src in [("chip_bench_trace_a", CPU_TRACE),
                      ("chip_bench_trace_b", PROGRAM_TRACE)]:
        dst = tmp_path / name / "plugins" / "profile" / "t"
        dst.mkdir(parents=True)
        shutil.copy(src, dst / "x.xplane.pb")
    run = {"trace": tracing.load(str(PROGRAM_TRACE), **CPU)}
    prog = program.of(run)
    assert prog is not None and run["program_trace"] is prog
    assert prog.window == run["trace"].span("bench.window")
    # the older recording has a window but no program events
    assert program.of({"trace": tracing.load(str(CPU_TRACE), **CPU)}) is None


def test_a_tiny_traced_run_reads_the_new_metrics():
    import chip_bench_tiny as tiny
    ckpt = tiny.run_tiny("tiny.train_ckpt", trace=True, save_every=2)
    assert set(CKPT) - set(DEVICE) <= set(ckpt["metrics"])
    resume = tiny.run_tiny("tiny.resume", trace=True, seconds=0.2)
    assert set(RESUME) <= set(resume["metrics"])


# --------------------------------------------------------------------------- #
# The harness's own reduction, as it was
# --------------------------------------------------------------------------- #
def test_the_older_recorded_trace_reduces_as_before():
    tr = tracing.load(str(CPU_TRACE), **CPU)
    win = tr.span("bench.window")
    assert [h[0] for h in tr.host] == PINNED["host"]
    assert win == pytest.approx(PINNED["window"], abs=1e-12)
    assert tracing.busy(tr, [win]) == pytest.approx(PINNED["busy"],
                                                    rel=1e-12)
    assert [n for n, _ in tracing.top_ops(tr, win)] == PINNED["ops"]
    assert [t for _, t in tracing.top_ops(tr, win)] == pytest.approx(
        PINNED["op_s"], rel=1e-9)
    assert dict(tracing.idle_by_host(tr, win)) == pytest.approx(
        PINNED["idle"], rel=1e-9)


# read with tracing.py as the benchmark first had it
PINNED = {
    "host": ["bench.window", "bench.train_span", "bench.input",
             "bench.train_span", "bench.input", "bench.save",
             "bench.train_span", "bench.input"],
    "window": (1.5717000000000002e-05, 0.011514462000000001),
    "busy": 0.0006586050000000009,
    "ops": ["dot_general", "wrapped_reduce-window", "wrapped_tanh",
            "wrapped_reduce"],
    "op_s": [0.0005222149999999995, 7.98640000000011e-05,
             5.314600000000044e-05, 3.379999999999876e-06],
    "idle": {"bench.input": 0.006219642000000001,
             "bench.save": 0.004051615999999999,
             "bench.train_span": 0.0005544709999999995,
             "bench.none": 1.4411000000000632e-05},
}
