"""Production training driver — one CLI over three substrate modes.

    # classic single-process training (real train step, TCE checkpoints):
    PYTHONPATH=src python -m repro.launch.train --arch llama3-8b --reduced \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

    # OLMo-1B at full width, depth cut to fit one TPU v5e; then resume:
    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --layers 6 \
        --batch 4 --seq 2048 --steps 8 --ckpt-every 4 --ckpt-dir /tmp/olmo
    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --layers 6 \
        --batch 4 --seq 2048 --steps 12 --ckpt-every 4 --ckpt-dir /tmp/olmo \
        --resume

    # real multi-process ranks under the full TOL/TEE/planner recovery
    # loop, with scripted SIGKILLs (the fault-tolerance capstone):
    PYTHONPATH=src python -m repro.launch.train --substrate process --tiny \
        --ranks 2 --spares 2 --steps 24 --ckpt-every 6 \
        --inject-kills 9:1,17:0 --json /tmp/run.json

    # the same protected run on the modelled cluster (seconds, no procs):
    PYTHONPATH=src python -m repro.launch.train --substrate sim --ranks 4 \
        --steps 40 --ckpt-every 10 --inject-kills 13:1,27:2

``--substrate single`` (default) is the in-process loop and the path that
runs on an accelerator: the real jitted train step on the backend's first
device, checkpointing through one local TCE rank (``TCEConfig(n_nodes=1,
backup=False)`` — there is no ring to back up to), resuming from the
freshest checkpoint with ``--resume``. ``chip_smoke.py`` at the repository
root drives the same functions (:func:`plan_steps`, :func:`train_span`,
:func:`open_tce`, :func:`restore_state`), and over a mesh.

``--substrate process`` runs each rank as its own CPU process (a chip
belongs to one process, so the ranks never take it).

``--substrate process|sim`` hand the run to the shared recovery driver
(:func:`repro.substrate.driver.run_protected`): the substrate is built by
:func:`repro.substrate.build_substrate` and the driver speaks only the
Substrate protocol, so the two modes are interchangeable end to end.
Exit code follows the shared convention: 0 iff the run completed.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import time
from typing import Callable

from repro.cli import (EXIT_FAILURE, EXIT_OK, EXIT_USAGE, base_parser,
                       list_catalog, write_reports)

DEFAULT_CKPT_DIR = os.path.join(tempfile.gettempdir(), "repro_ckpt")
# bound on waiting for a checkpoint to reach the store (a full-width state
# is gigabytes of host->disk traffic)
PERSIST_TIMEOUT_S = 600.0

SUBSTRATES = {
    "single": "in-process training loop, local TCE checkpoints (--resume)",
    "process": "real multi-process JAX ranks + TOL/TEE recovery driver",
    "sim": "modelled cluster under the same recovery driver",
}


def scale_config(cfg, args):
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    return cfg


def build_argparser():
    ap = base_parser("python -m repro.launch.train",
                     "Train a model, optionally under fault-tolerant "
                     "recovery (substrate modes: single | process | sim).")
    ap.add_argument("--substrate", default="single",
                    choices=sorted(SUBSTRATES),
                    help="where the ranks run (default: single)")
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--reduced", action="store_true",
                    help="shrink the arch to its reduced test size")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="shorthand for --reduced --layers 1 with a small "
                         "batch/seq (fast smoke runs)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint directory (default: repro_ckpt in the "
                         "temp dir for single mode, a fresh tempdir "
                         "otherwise)")
    ap.add_argument("--codec", default="raw",
                    help="TCE persist codec (raw|zlib|int8)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the freshest checkpoint (single mode)")
    ap.add_argument("--log-every", type=int, default=10)
    # protected-mode knobs (process/sim)
    ap.add_argument("--ranks", type=int, default=2,
                    help="gang size for process/sim substrates")
    ap.add_argument("--spares", type=int, default=2,
                    help="replacement pool size for process/sim substrates")
    ap.add_argument("--inject-kills", default="", metavar="SPECS",
                    help="scripted faults 'STEP:RANK[:CATEGORY],...' "
                         "(process/sim modes)")
    ap.add_argument("--inject-stalls", default="", metavar="SPECS",
                    help="scripted stragglers 'STEP:RANK[:SECONDS],...' — "
                         "SIGSTOP/SIGCONT a live rank so the streaming TEE "
                         "sees a genuinely slow rank (process/sim modes)")
    return ap


def _apply_tiny(args) -> None:
    if args.tiny:
        args.reduced = True
        args.layers = args.layers or 1
        args.batch = min(args.batch, 2)
        args.seq = min(args.seq, 16)


# --------------------------------------------------------------------------- #
# Single mode: one process drives the devices it sees
# --------------------------------------------------------------------------- #
@dataclasses.dataclass
class StepPlan:
    """The jitted entry points of one run, on one device or over a mesh."""
    init: Callable      # key -> TrainState on the plan's devices
    step: Callable      # (state, batch) -> (state, metrics); donates state
    place: Callable     # host pytree -> TrainState on the plan's devices


def build_configs(args):
    """(model config, optimizer config) of a run from its CLI args."""
    from repro.configs import get_config
    from repro.train import AdamConfig

    cfg = scale_config(get_config(args.arch), args)
    opt_cfg = AdamConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                         decay_steps=args.steps)
    return cfg, opt_cfg


def plan_steps(cfg, opt_cfg, batch: int, seq: int, mesh=None) -> StepPlan:
    """Jit the state init and the train step. With a ``mesh``, state and
    batch are sharded by the default (megatron) logical-axis rules of
    :mod:`repro.parallel.sharding`, and the model's activation constraints
    trace under the same rules."""
    import jax

    from repro.launch.specs import batch_specs
    from repro.parallel import sharding as shd
    from repro.train import (TrainConfig, init_train_state, make_train_step,
                             train_state_axes, train_state_shapes)

    step = make_train_step(cfg, opt_cfg, TrainConfig())

    def init(key):
        return init_train_state(cfg, opt_cfg, key)

    if mesh is None:
        return StepPlan(jax.jit(init), jax.jit(step, donate_argnums=(0,)),
                        jax.device_put)
    state_sh = shd.tree_shardings(train_state_axes(cfg, opt_cfg),
                                  train_state_shapes(cfg, opt_cfg), mesh)
    shapes, axes = batch_specs(cfg, batch, seq, with_labels=True)
    batch_sh = shd.tree_shardings(axes, shapes, mesh)

    def sharded_step(state, b):
        with shd.use_sharding(mesh):
            return step(state, b)

    return StepPlan(
        jax.jit(init, out_shardings=state_sh),
        jax.jit(sharded_step, in_shardings=(state_sh, batch_sh),
                donate_argnums=(0,)),
        lambda tree: jax.device_put(tree, state_sh))


def make_batch(cfg, data, step: int):
    """Host batch ``step`` of the synthetic stream, with the zero encoder /
    vision embeddings the encdec and VLM families take."""
    import numpy as np

    batch = data.batch_at(step)
    b = data.batch
    if cfg.family == "encdec":
        batch["enc_embeds"] = np.zeros((b, cfg.encdec.enc_len, cfg.d_model),
                                       np.float32)
    if cfg.family == "vlm":
        batch["vision_embeds"] = np.zeros(
            (b, min(cfg.vlm.n_vision_tokens, data.seq), cfg.d_model),
            np.float32)
    return batch


def open_tce(args, state_bytes: int):
    """The run's local TCE rank: one node, no ring (there is no second
    machine to back up to), host cache sized to hold its cached steps."""
    from repro.core.tce import DiskStore, TCEConfig, TCEngine

    cycles = TCEConfig.max_cycles
    mem = max(TCEConfig.mem_limit_bytes, cycles * state_bytes + (64 << 20))
    return TCEngine(TCEConfig(n_nodes=1, backup=False, codec=args.codec,
                              mem_limit_bytes=mem,
                              durability_timeout_s=PERSIST_TIMEOUT_S),
                    DiskStore(args.ckpt_dir or DEFAULT_CKPT_DIR))


def tree_nbytes(tree) -> int:
    """Bytes of a tree of arrays or ShapeDtypeStructs."""
    import math

    import jax
    return sum(math.prod(x.shape) * x.dtype.itemsize
               for x in jax.tree.leaves(tree))


def restore_state(tce, cfg, opt_cfg):
    """Freshest checkpoint -> (step, TrainState of host arrays). Raises
    FileNotFoundError when there is none."""
    from repro import obs
    from repro.core.tce.engine import unflatten_like
    from repro.train import train_state_shapes

    ck_step, flat = tce.restore()
    shapes = train_state_shapes(cfg, opt_cfg)
    with obs.span("transom.restore.unflatten"):
        return int(ck_step), unflatten_like(shapes, flat)


def train_span(plan: StepPlan, state, data, cfg, start: int, stop: int, *,
               tce=None, ckpt_every: int = 0, log_every: int = 1):
    """Steps [start, stop): each step's batch is built on the host, then the
    step runs and is waited for. Saves through ``tce`` after every
    ``ckpt_every``-th step. Returns (state, [(step, loss, step_s)])."""
    import jax

    from repro import obs

    records = []
    for step in range(start, stop):
        batch = make_batch(cfg, data, step)
        t0 = time.perf_counter()
        with obs.step_span(step):
            state, metrics = plan.step(state, batch)
            jax.block_until_ready((state, metrics))
        dt = time.perf_counter() - t0
        loss = float(metrics["loss"])
        records.append((step + 1, loss, dt))
        if (step + 1) % log_every == 0 or step == start:
            print(f"step {step+1:5d} loss={loss:.4f} "
                  f"gnorm={float(metrics['grad_norm']):.3f} "
                  f"lr={float(metrics['lr']):.2e} ({dt:.3f}s/step)",
                  flush=True)
        if tce is not None and ckpt_every and (step + 1) % ckpt_every == 0:
            h = tce.save(step + 1, state)
            print(f"  tce.save(step={step+1}) "
                  f"cache={h.cache_wall_s*1e3:.0f}ms "
                  f"(async persist in background)", flush=True)
    return state, records


def run_single(args) -> int:
    """The in-process loop: the real jitted step, one local TCE rank."""
    import jax

    from repro.data import SyntheticLMData

    cfg, opt_cfg = build_configs(args)
    print(f"arch={cfg.name} params={cfg.n_params():,} "
          f"devices={jax.device_count()}")
    plan = plan_steps(cfg, opt_cfg, args.batch, args.seq)
    data = SyntheticLMData(cfg.vocab_size, args.seq, args.batch, args.seed)
    state_bytes = tree_nbytes(jax.eval_shape(plan.init,
                                             jax.random.key(args.seed)))
    tce = open_tce(args, state_bytes)
    start, state = 0, None
    if args.resume:
        try:
            start, host_state = restore_state(tce, cfg, opt_cfg)
            state = plan.place(host_state)
            data.restore(type(data.state)(start))
            print(f"resumed from step {start}")
        except FileNotFoundError:
            print("no checkpoint found; starting fresh")
    if state is None:
        state = plan.init(jax.random.key(args.seed))

    t0 = time.time()
    state, records = train_span(plan, state, data, cfg, start, args.steps,
                                tce=tce, ckpt_every=args.ckpt_every,
                                log_every=args.log_every)
    durable = tce.reconciler.quiesce(PERSIST_TIMEOUT_S)
    tce.close()
    if not durable:
        print("error: checkpoints not persisted within "
              f"{PERSIST_TIMEOUT_S:.0f}s", file=sys.stderr)
        return EXIT_FAILURE
    final_loss = records[-1][1] if records else None
    if args.json or args.out:
        from repro.report import finalize
        rep = finalize({"completed": True, "steps_done": args.steps,
                        "total_steps": args.steps, "arch": cfg.name,
                        "final_loss": final_loss,
                        "measured": {"wall_s": round(time.time() - t0, 3)}},
                       engine="train", scenario="single", seed=args.seed)
        write_reports([rep], json_path=args.json, out_dir=args.out)
    print("done.")
    return EXIT_OK


# --------------------------------------------------------------------------- #
def run_protected_mode(args) -> int:
    """process/sim substrates under the shared recovery driver."""
    from repro.substrate import build_substrate
    from repro.substrate.driver import (DriveConfig, KillSpec, StallSpec,
                                        run_protected)

    try:
        kills = KillSpec.parse_list(args.inject_kills)
        stalls = StallSpec.parse_list(args.inject_stalls)
    except ValueError as e:
        print(f"error: {e}", file=sys.stderr)
        return EXIT_USAGE

    if args.substrate == "process":
        sub = build_substrate(
            "process", n_ranks=args.ranks, n_spares=args.spares,
            ckpt_dir=args.ckpt_dir, seed=args.seed, arch=args.arch,
            layers=args.layers or 1, batch=args.batch, seq=args.seq,
            lr=args.lr, total_steps=args.steps, codec=args.codec)
    else:
        sub = build_substrate("sim", n_nodes=args.ranks,
                              n_spares=args.spares,
                              store_root=args.ckpt_dir)
    cfg = DriveConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      seed=args.seed,
                      scenario=f"train_{args.substrate}")
    try:
        rep = run_protected(sub, cfg, kills, stalls)
    finally:
        sub.close()
    shown = {k: rep[k] for k in ("engine", "scenario", "seed", "completed",
                                 "steps_done", "lost_steps", "restarts",
                                 "final_loss", "timeline_digest")}
    shown["decisions"] = rep["decisions"]["by_decision"]
    print(json.dumps(shown, indent=2, sort_keys=True))
    write_reports([rep], json_path=args.json, out_dir=args.out)
    return EXIT_OK if rep["completed"] else EXIT_FAILURE


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    if args.list:
        return list_catalog(
            SUBSTRATES, prog="python -m repro.launch.train",
            what="substrate modes",
            hint="python -m repro.launch.train --substrate <name>")
    _apply_tiny(args)
    if args.substrate == "single":
        from repro.launch.compile_cache import setup_compile_cache
        setup_compile_cache()
        return run_single(args)
    return run_protected_mode(args)


if __name__ == "__main__":
    sys.exit(main())
