"""The benchmark's runner: one cell, one seed, one window.

A cell is ``<config>.<mix>``. Its configuration is a file of sizes under
``configs/``, its traffic a file of parameters under ``traffic/``, each of
its metrics a reader under ``metrics/``; this module knows none of them by
name. Two optional keys of a configuration say how it runs and is checked:

  layout     mesh axes and sizes over the cell's chips, e.g. {"data": 2,
             "model": 2}: the step is planned over that mesh under the
             program's default sharding rules; without it, on one device
  reference  {"module": the module of this package that holds the
             configuration's plain reference (default "reference"),
             "flops": the module that counts its FLOPs (default "flops"),
             "options": keyword options of the reference's ``train``}

What a configuration may cut from its source is one rule,
``check_cuts``, checked wherever a cell resolves. A key of ``model`` that
holds a dict is merged field by field into the registry's block of that
name (``build_model``).

A reference module's ``train(model, opt, seed, batches, *, devices,
state_dtype, compute_dtype, half_batch, **options)`` returns what
``compare`` takes, with leaves named as ``reference.slice_sq_norms`` names
them. A traffic file selects the job's phases:

  check_steps  the first steps, compared afterwards with the reference
  save_every   a TCE save after every that many steps (0: none)
  resume       the window repeats kill -> resume -> one step; set-up
               makes one such resume, untimed, first
  warm_steps   steps trained (and saved) before a resume window
  codec        the TCE persist codec

The window drives ``repro.launch.train``'s own functions: ``train_span``
(one step per call, so the window can close on time), with the engine from
``open_tce`` wrapped so each save is timed where the loop calls it; and for
a resume ``open_tce``, ``restore_state``, ``StepPlan.place`` and one
``train_span`` step.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Dict, List, Optional

import numpy as np

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
COMMIT_TIMEOUT_S = 600.0


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# --------------------------------------------------------------------------- #
# Resolving a cell by name
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[tuple]          # (spec, read)
    per_layer: List[tuple]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_reader(name: str, here: Path = HERE) -> Callable:
    path = here / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def resolve(name: str, bench: Optional[dict] = None, root: Path = ROOT,
            here: Path = HERE) -> Cell:
    bench = bench if bench is not None else load_benchmark(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    check_cuts(conf, config)
    traffic = json.loads((here / "traffic" / f"{w['traffic']}.json")
                         .read_text())

    def readers(specs):
        return [(m, load_reader(m["name"], here)) for m in specs
                if name in m.get("workloads", [name])]

    return Cell(name, w["chips"], config, traffic,
                readers(bench["end_to_end"]), readers(bench["per_layer"]))


# --------------------------------------------------------------------------- #
# What a configuration may cut from its source
# --------------------------------------------------------------------------- #
WIDTHS = ("d_model", "d_head", "d_ff")
WIDTH_BLOCKS = ("ssm", "mla", "moe")     # every field a width, but:
EXPERTS = "moe.n_experts"                # the count of routed experts
SHARE_KEYS = ("vocab_size", "n_heads", EXPERTS)
ALSO_NAMED = {"batch_tokens": "batch"}   # published key: its name in reduced


def flat(tree: dict, prefix: str = "") -> dict:
    """Nested blocks' keys as ``block.field``."""
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}."))
        else:
            out[prefix + k] = v
    return out


def is_width(key: str) -> bool:
    block, dot, _ = key.partition(".")
    return key in WIDTHS or (block in WIDTH_BLOCKS and dot == "." and
                             key != EXPERTS)


def check_cuts(conf: dict, config: dict) -> None:
    """Raises ValueError where the configuration file ``config`` of the
    BENCHMARK.json entry ``conf`` cuts its source by more than it may.

    The configuration run is ``build_model``'s, nested blocks flattened to
    ``block.field``, with the file's ``seq`` and ``batch_tokens`` (batch x
    seq, named ``batch`` in ``reduced``). ``published`` states every width
    it has (``WIDTHS``, every field of ``ssm``, ``mla`` and ``moe`` but the
    count of experts), and every key of ``published`` equals the value run
    unless ``reduced`` lists it or its block. ``reduced`` names no width and
    no block that holds one, and no width differs, listed or not. A chip's
    share of a layer (``SHARE_KEYS``; ``n_heads`` with ``n_kv_heads``) may
    be cut only where ``share`` states it: ``{key: {"chips": c, "how":
    ...}}``, with the value run x c = the published count exactly (for
    ``n_heads``, the key-value heads too), at least an eighth of the
    vocabulary and at least 8 routed experts. A file with no ``published``
    block is its own source (the test copies): it may cut nothing."""
    name = conf["name"]

    def fail(msg):
        raise ValueError(f"configuration {name}: {msg}")

    reduced = config.get("reduced", [])
    if config.get("name") != name or reduced != conf.get("reduced", []):
        fail("its file's name and reduced differ from BENCHMARK.json's")
    run = flat(dict(dataclasses.asdict(build_model(config)[0]),
                    seq=config["seq"],
                    batch_tokens=config["batch"] * config["seq"]))
    published = flat(config["published"]) if "published" in config else run
    shares = config.get("share", {})
    for k in reduced:
        if is_width(k) or any(is_width(w) for w in run
                              if w.startswith(k + ".")):
            fail(f"reduced names {k}, which is or holds a width")
    for k in run:
        if is_width(k) and k not in published:
            fail(f"width {k} runs at {run[k]} and published does not "
                 f"state it")
    for k, want in published.items():
        if k not in run:
            fail(f"published {k} is not in the configuration run")
        if run[k] == want:
            continue
        if is_width(k):
            fail(f"width {k} is {run[k]}, published {want}")
        if not {k, k.split(".")[0], ALSO_NAMED.get(k)} & set(reduced):
            fail(f"{k} is {run[k]}, published {want}, and reduced does "
                 f"not list it")
        if k in SHARE_KEYS + ("n_kv_heads",) and \
                ("n_heads" if k == "n_kv_heads" else k) not in shares:
            fail(f"{k} is cut with no share entry stating it")
    for k, s in shares.items():
        if k not in SHARE_KEYS or k not in reduced or k not in run:
            fail(f"share {k}: not a share key, not in reduced, or not run")
        held, chips, total = run[k], s["chips"], published.get(k)
        if held * chips != total:
            fail(f"share {k}: {held} held x {chips} chips must equal the "
                 f"published {total}")
        if k == "n_heads" and \
                run["n_kv_heads"] * chips != published.get("n_kv_heads"):
            fail("share n_heads: the key-value heads are not divided alike")
        if k == "vocab_size" and held * 8 < total:
            fail(f"share vocab_size: {held} rows, under an eighth of {total}")
        if k == EXPERTS and held < 8:
            fail(f"share {EXPERTS}: {held} experts held, under 8")


# --------------------------------------------------------------------------- #
# Devices, peaks
# --------------------------------------------------------------------------- #
def devices_for(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    return devs[:chips]


def layout_mesh(config: dict, devices):
    """The mesh the configuration's ``layout`` asks for over ``devices``,
    or None where it has no layout."""
    layout = config.get("layout")
    if not layout:
        return None
    if math.prod(layout.values()) != len(devices):
        raise ValueError(f"layout {layout} does not cover {len(devices)} "
                         f"chips")
    from repro.launch.mesh import make_mesh
    return make_mesh(tuple(layout.values()), tuple(layout), devices=devices)


def layout_chips(config: dict) -> int:
    return math.prod((config.get("layout") or {}).values())


def plan_of(lt, config: dict, cfg, opt_cfg, devices):
    """``plan_steps`` of the configuration: over its layout's mesh, or, with
    no layout, called as a one-device plan."""
    mesh = layout_mesh(config, devices)
    batch, seq = config["batch"], config["seq"]
    if mesh is None:
        return lt.plan_steps(cfg, opt_cfg, batch, seq)
    return lt.plan_steps(cfg, opt_cfg, batch, seq, mesh=mesh)


def reference_of(config: dict):
    """(the configuration's reference module, its ``train`` options, its
    FLOP count ``train_step_flops``)."""
    ref = config.get("reference", {})
    mod = importlib.import_module("chip." + ref.get("module", "reference"))
    flops = importlib.import_module("chip." + ref.get("flops", "flops"))
    return mod, ref.get("options", {}), flops.train_step_flops


def peak_flops(kind: str, here: Path = HERE) -> float:
    peaks = json.loads((here / "peaks.json").read_text())["devices"]
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in peaks.json")
    return float(peaks[kind]["bf16_flops_per_s"])


# --------------------------------------------------------------------------- #
# Exact fingerprints of a state
# --------------------------------------------------------------------------- #
_MIX = 2654435761


def fingerprint(tree):
    """Per leaf: (sum of the 32-bit words, sum of words x position hash),
    both mod 2**32. One jitted program over the device state."""
    import jax
    import jax.numpy as jnp

    def one(x):
        if x.dtype.itemsize == 4:
            u = jax.lax.bitcast_convert_type(x, jnp.uint32)
        elif x.dtype.itemsize == 2:
            u = jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
        else:
            u = x.astype(jnp.uint32)
        u = u.reshape(-1)
        w = jax.lax.iota(jnp.uint32, u.size) * jnp.uint32(_MIX) + jnp.uint32(1)
        return jnp.stack([jnp.sum(u, dtype=jnp.uint32),
                          jnp.sum(u * w, dtype=jnp.uint32)])

    return [one(x) for x in jax.tree.leaves(tree)]


def leaves_differ(a, b) -> int:
    if len(a) != len(b):
        return max(len(a), len(b))
    return sum(int(not np.array_equal(np.asarray(x), np.asarray(y)))
               for x, y in zip(a, b))


# --------------------------------------------------------------------------- #
# The engine, with each save timed where the loop calls it
# --------------------------------------------------------------------------- #
class TimedSaves:
    """Wraps the run's ``TCEngine``: times each ``save`` call (the loop's
    stall), takes the saved state's fingerprint on the device before the
    clock starts, and stamps on the host clock the moment the store shows
    each save's manifest (durable)."""

    def __init__(self, engine, fp: Callable):
        from jax.profiler import TraceAnnotation
        self._engine, self._fp, self._ta = engine, fp, TraceAnnotation
        self.saves: List[dict] = []
        self._stop = threading.Event()
        self._watch = threading.Thread(target=self._watch_commits,
                                       daemon=True)
        self._watch.start()

    def __getattr__(self, name):
        return getattr(self._engine, name)

    def save(self, step, state, **kw):
        fp = self._fp(state)
        with self._ta("bench.save"):
            wall0, t0 = time.time(), time.perf_counter()
            h = self._engine.save(step, state, **kw)
            stall = time.perf_counter() - t0
        self.saves.append({"step": int(step), "start": wall0,
                           "end": wall0 + stall, "stall_s": stall,
                           "cache_write_s": h.cache_wall_s, "commit": None,
                           "fingerprint": [np.asarray(x) for x in fp]})
        return h

    def _watch_commits(self):
        while not self._stop.wait(0.01):
            for s in list(self.saves):
                if s["commit"] is None and \
                        self._engine.store.has_step(s["step"]):
                    s["commit"] = time.time()

    def wait_commits(self, timeout: float = COMMIT_TIMEOUT_S) -> bool:
        deadline = time.time() + timeout
        while time.time() < deadline:
            if all(s["commit"] is not None for s in self.saves):
                return True
            time.sleep(0.01)
        return False

    def close(self):
        self._stop.set()
        self._watch.join()
        self._engine.close()


# --------------------------------------------------------------------------- #
# Comparisons
# --------------------------------------------------------------------------- #
def leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
             keep: Optional[set] = None) -> float:
    """Worst leaf: |program's norm - reference's| over the larger of the
    reference's norm of that leaf and of the median leaf."""
    med = float(np.median(list(ref.values())))
    names = [k for k in ref if keep is None or k in keep]
    if set(prog) != set(ref):
        return math.inf
    return max(abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
               for k in names)


def first_steps(lt, plan, cfg, data, state, n: int, sq_norms, b1: float):
    """Steps 1..n through ``train_span``, reading what the comparison
    needs: each step's loss, the first gradient as the optimizer got it
    (from Adam's first moment after one step, m = (1 - b1) g), and the
    weights before and after (host copies)."""
    import jax
    p0 = jax.device_get(state.params)
    state, rec = lt.train_span(plan, state, data, cfg, 0, 1)
    grad = {k: math.sqrt(float(v)) / (1.0 - b1)
            for k, v in sq_norms(state.opt["m"]).items()}
    state, rec2 = lt.train_span(plan, state, data, cfg, 1, n)
    return state, {"losses": [r[1] for r in rec + rec2], "grad_norms": grad,
                   "p0": p0, "p_after": jax.device_get(state.params)}


def program_numbers(readings: dict) -> dict:
    """Readings -> losses, gradient norms, change norms (host work)."""
    change = change_sq_norms(readings["p_after"], readings["p0"])
    return {"losses": readings["losses"], "grad_norms": readings["grad_norms"],
            "change_norms": {k: math.sqrt(v) for k, v in change.items()}}


def compare(prog: dict, ref: dict) -> dict:
    """The three numbers a training cell compares with the reference: the
    worst step's relative loss gap, and the worst leaf's gap in the norm of
    the first gradient and of the weights' change. Leaves whose reference
    gradient is under a thousandth of the median leaf's are left out of the
    change (they move by round-off alone)."""
    losses = [abs(a - b) / abs(b) for a, b in
              zip(prog["losses"], ref["losses"])]
    if len(prog["losses"]) != len(ref["losses"]):
        losses.append(math.inf)
    med = float(np.median(list(ref["grad_norms"].values())))
    keep = {k for k, g in ref["grad_norms"].items() if g >= 1e-3 * med}
    return {"loss_gap": max(losses),
            "grad_norm_gap": leaf_gap(prog["grad_norms"], ref["grad_norms"]),
            "update_norm_gap": leaf_gap(prog["change_norms"],
                                        ref["change_norms"], keep)}


def change_sq_norms(after, before) -> Dict[str, float]:
    """Squared norm of ``after - before`` per leaf and per layer of a
    stacked leaf, in float64 on the host, named as
    ``reference.slice_sq_norms`` names them."""
    import jax

    from chip.reference import leaf_name
    out = {}
    flat_b = jax.tree.leaves(before)
    for (kp, a), b in zip(jax.tree_util.tree_flatten_with_path(after)[0],
                          flat_b):
        name = leaf_name(kp)
        a, b = np.asarray(a), np.asarray(b)
        rows = a.reshape(a.shape[0], -1) if a.ndim >= 3 else a.reshape(1, -1)
        brow = b.reshape(rows.shape)
        per = [float(np.sum(np.square(r.astype(np.float64) - s)))
               for r, s in zip(rows, brow)]
        if a.ndim >= 3:
            out.update({f"{name}[{i}]": v for i, v in enumerate(per)})
        else:
            out[name] = per[0]
    return out


# --------------------------------------------------------------------------- #
# One resume
# --------------------------------------------------------------------------- #
def resume_once(lt, plan, cfg, opt_cfg, data, state, tce, tce_args, nbytes,
                fp):
    """Kill (device state deleted, engine closed, JAX's in-memory caches
    cleared), then ``open_tce`` on the same store, ``restore_state``,
    ``place`` and one ``train_span`` step. Returns (state, engine, record)."""
    import jax
    from jax.profiler import TraceAnnotation as TA
    t0 = time.perf_counter()
    with TA("bench.kill"):
        free(state)
        tce.close()
        jax.clear_caches()
    with TA("bench.open"):
        tce = lt.open_tce(tce_args, nbytes)
    t1 = time.perf_counter()
    with TA("bench.restore"):
        got, host = lt.restore_state(tce, cfg, opt_cfg)
    t2 = time.perf_counter()
    with TA("bench.place"):
        state = plan.place(host)
        jax.block_until_ready(state)
    t3 = time.perf_counter()
    del host
    data.restore(got)
    with TA("bench.train_span"):
        state, rec = lt.train_span(plan, state, data, cfg, data.position,
                                   data.position + 1)
    t4 = time.perf_counter()
    return state, tce, {
        "total_s": t4 - t0, "restore_s": t2 - t1, "place_s": t3 - t2,
        "first_step_s": t4 - t3, "step": got, "loss": rec[0][1],
        "fingerprint": [np.asarray(x) for x in fp(state)]}


def step_footprint(plan, state, batch) -> int:
    """Device bytes the compiled step needs: arguments, temporaries and
    outputs, less the outputs that reuse donated arguments. Lowering reads
    only the arguments' shapes: nothing runs or is donated."""
    m = plan.step.lower(state, batch).compile().memory_analysis()
    return int(m.argument_size_in_bytes + m.temp_size_in_bytes
               + m.output_size_in_bytes - m.alias_size_in_bytes)


# --------------------------------------------------------------------------- #
# One run
# --------------------------------------------------------------------------- #
def build_model(config: dict):
    """(model config, optimizer config): the registry's ``arch`` with
    ``model``'s keys replaced; a key that holds a dict replaces fields of
    the registry's block of that name."""
    from repro.configs import get_config
    from repro.train import AdamConfig
    base = get_config(config["arch"])
    model = {}
    for k, v in config["model"].items():
        if isinstance(v, dict):
            block = getattr(base, k, None)
            if not dataclasses.is_dataclass(block):
                raise ValueError(f"model.{k}: {config['arch']} has no {k} "
                                 f"block")
            unknown = set(v) - {f.name for f in dataclasses.fields(block)}
            if unknown:
                raise ValueError(f"model.{k}: no fields {sorted(unknown)}")
            v = dataclasses.replace(block, **v)
        model[k] = v
    cfg = dataclasses.replace(base, **model)
    return cfg, AdamConfig(**config["optimizer"])


def free(tree) -> None:
    import jax
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array) and not x.is_deleted():
            x.delete()


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, *, require_tpu: bool = True) -> dict:
    """Set up, measure for ``seconds``, check. Returns the result line."""
    import jax
    from jax.profiler import TraceAnnotation as TA

    devices = devices_for(cell.chips, require_tpu)
    from repro.launch import train as lt
    from repro.launch.compile_cache import setup_compile_cache

    from chip import reference, tracing
    from chip.tokens import TokenStream

    setup_compile_cache()
    phase_t0 = t_start

    def phase(what):            # how long each part of the run took
        nonlocal phase_t0
        now = time.perf_counter()
        print(f"[bench] {what}: {now - phase_t0:.3f} s", file=sys.stderr,
              flush=True)
        phase_t0 = now

    conf, traf = cell.config, cell.traffic
    ref, ref_options, train_step_flops = reference_of(conf)
    cfg, opt_cfg = build_model(conf)
    batch, seq = conf["batch"], conf["seq"]
    check_steps = traf.get("check_steps", 0)
    every = traf.get("save_every", 0)
    resume = traf.get("resume", False)

    plan = plan_of(lt, conf, cfg, opt_cfg, devices)
    data = TokenStream.from_traffic(traf, cfg.vocab_size, seq, batch, seed)
    state = plan.init(jax.random.key(seed))
    fp = jax.jit(fingerprint).lower(state).compile()
    sq_norms = jax.jit(reference.slice_sq_norms)
    store_dir = tempfile.mkdtemp(prefix="chip_bench_store_")
    tce_args = SimpleNamespace(codec=traf.get("codec", "raw"),
                               ckpt_dir=store_dir)
    nbytes = lt.tree_nbytes(state)
    tce = None
    readings: dict = {}
    checks: Dict[str, tuple] = {}
    prof_dir = None
    try:
        step = 0
        if check_steps:
            state, readings = first_steps(lt, plan, cfg, data, state,
                                          check_steps, sq_norms, opt_cfg.b1)
            step = check_steps
        if every or resume:
            tce = TimedSaves(lt.open_tce(tce_args, nbytes), fp)
        if resume:
            warm = traf["warm_steps"]
            state, _ = lt.train_span(plan, state, data, cfg, step, warm)
            tce.save(warm, state)
            if not tce.wait_commits():
                raise RuntimeError("the warm-up save never became durable")
            state, rec = lt.train_span(plan, state, data, cfg, warm,
                                       warm + 1)
            live = (rec[0][1], [np.asarray(x) for x in fp(state)])
            step = warm
            # one resume before the window: the process's first restore,
            # place and reload of the step are set-up, not the measure
            state, tce, first = resume_once(lt, plan, cfg, opt_cfg, data,
                                            state, tce, tce_args, nbytes, fp)
            print(f"[bench] untimed resume: {first['total_s']:.3f} s",
                  file=sys.stderr, flush=True)
        one_batch = lt.make_batch(cfg, data, step)
        jax.block_until_ready(state)
        phase("set-up")

        data.calls.clear()
        if trace:
            prof_dir = tempfile.mkdtemp(prefix="chip_bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(prof_dir, profiler_options=opts)
        records: List[tuple] = []
        resumes: List[dict] = []
        t_open = time.perf_counter()
        setup_s = t_open - t_start
        with TA("bench.window"):
            while True:
                if resume:
                    state, tce, r = resume_once(lt, plan, cfg, opt_cfg, data,
                                                state, tce, tce_args, nbytes,
                                                fp)
                    resumes.append(r)
                else:
                    with TA("bench.train_span"):
                        state, rec = lt.train_span(
                            plan, state, data, cfg, step, step + 1,
                            tce=tce, ckpt_every=every)
                    records.extend(rec)
                    step += 1
                if time.perf_counter() - t_open >= seconds:
                    break
        window_s = time.perf_counter() - t_open
        tr = None
        if trace:
            jax.profiler.stop_trace()
            tr = tracing.load(tracing.xplane_path(prof_dir))

        phase(f"window ({len(records)} steps, {len(resumes)} resumes "
              f"{[round(r['total_s'], 3) for r in resumes]})")
        saves = list(tce.saves) if isinstance(tce, TimedSaves) else []
        if saves and not tce.wait_commits():
            raise RuntimeError("a save of the window never became durable")
        phase(f"durable wait ({len(saves)} saves)")
        stats = [d.memory_stats() or {} for d in devices]
        peak = max(m.get("peak_bytes_in_use", 0) for m in stats)
        print(f"[bench] memory_stats: {stats}", file=sys.stderr, flush=True)
        footprint = step_footprint(plan, state, one_batch)
        phase(f"step footprint ({footprint} B)")

        # -- correctness ----------------------------------------------------
        if saves:
            # the last save, read back from the store by a fresh engine
            back = lt.open_tce(tce_args, nbytes)
            try:
                got, host = lt.restore_state(back, cfg, opt_cfg)
            finally:
                back.close()
            last = saves[-1]
            back_state = plan.place(host)
            del host
            bad = leaves_differ(fp(back_state), last["fingerprint"])
            free(back_state)
            checks["ckpt_leaves_differ"] = (
                bad if got == last["step"] else len(last["fingerprint"]), 0)
            phase("read-back of the last save")
        if resume:
            want_loss, want_fp = live
            checks["resume_loss_gap"] = (max(
                abs(r["loss"] - want_loss) if r["step"] == warm else math.inf
                for r in [first] + resumes), 0)
            checks["resume_leaves_differ"] = (max(
                leaves_differ(r["fingerprint"], want_fp)
                for r in [first] + resumes), 0)
        if tce is not None:
            tce.close()
            tce = None
        free(state)
        del state
        if check_steps:
            nums = compare(program_numbers(readings), ref.train(
                conf["model"], conf["optimizer"], seed,
                data.rows(range(check_steps)), devices=devices,
                **ref_options))
            for k, v in nums.items():
                checks[k] = (v, conf["limits"][k])
            phase("reference")

        # -- metrics ------------------------------------------------------
        kind = devices[0].device_kind
        run_rec = {
            "cell": cell.name, "chips": len(devices),
            "layout": conf.get("layout"), "seconds": seconds,
            "window_s": window_s, "setup_s": setup_s,
            "steps": [{"step": s, "loss": l, "dt": d} for s, l, d in records],
            "tokens_per_step": batch * seq, "input_s": list(data.calls),
            "saves": saves, "resumes": resumes, "trace": tr,
            "flops_per_step": train_step_flops(conf["model"], batch, seq),
            "peak_flops": peak_flops(kind) if require_tpu else None}
        specs = cell.per_layer if trace else cell.end_to_end
        metrics = {}
        for spec, read in specs:
            val = read(run_rec)
            if val is None:
                if not trace:
                    raise RuntimeError(f"{spec['name']}: nothing to read")
                continue
            metrics[spec["name"]] = {"value": val, "unit": spec["unit"]}

        losses = [r[1] for r in records] + [r["loss"] for r in resumes]
        failed = sum(1 for x in losses if not math.isfinite(x))
        correct = failed == 0 and all(v <= lim for v, lim in checks.values())
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(jax.devices()), "memory_peak_bytes": peak,
                  "step_footprint_bytes": footprint,
                  "layout": conf.get("layout")}
        out = {"correct": correct, "attempted": len(losses),
               "failed": failed, "metrics": metrics, "device": device}
        if tr is not None:
            win = tr.span("bench.window")
            device["busy_s"] = tracing.busy(tr, [win])
            device["window_s"] = win[1] - win[0]
            out["breakdown"] = {"device_ops": tracing.top_ops(tr, win),
                                "idle_gaps": tracing.idle_by_host(tr, win)}
        out["checks"] = {k: {"value": v, "limit": lim}
                         for k, (v, lim) in checks.items()}
        return out
    finally:
        if tce is not None:
            tce.close()
        shutil.rmtree(store_dir, ignore_errors=True)
        if prof_dir:
            shutil.rmtree(prof_dir, ignore_errors=True)


def main(args, t_start: float) -> int:
    try:
        cell = resolve(args.workload)
    except KeyError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    try:
        with contextlib.redirect_stdout(sys.stderr):
            out = run(cell, args.seed, args.seconds, bool(args.trace),
                      t_start)
    except NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
