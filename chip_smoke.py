"""Smoke test of the training path on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the sharded path on a 2x2 mesh

One chip: OLMo-1B at its full width (d_model 2048, 16 heads of 128, d_ff
8192, vocab 50304, fp32 params and Adam moments) with its depth cut to fit
one 16 GB v5e, batch 4 x 2048 tokens, random weights from a seed. It runs
the functions ``python -m repro.launch.train --substrate single`` runs:

  (a) device check: the first device must be a TPU, or the script exits
      non-zero before any other phase;
  (b) train a few steps, printing each loss and its block_until_ready time;
  (c) TCE save at step K, reconciler quiesced (persisted to a DiskStore),
      restore through a fresh TCEngine on the same store, as --resume does:
      every restored leaf must be bit-equal to the host copy taken at save
      time, and step K+1 from the restored state must give the loss step
      K+1 gave from the live state;
  (d) one int8 codec round trip (encode_shard/decode_shard) on a
      full-width leaf, with the quant_blockwise kernel compiled, not
      interpreted.

--four-chips runs only the sharded path: the same config on a (data=2,
model=2) mesh with the default megatron rules against the same config on
devices[0], the per-device memory split, a TCE save and restore of the
sharded state with one resumed step, and OLMo-1B at its full 16 layers,
which one chip cannot hold, for two steps.

Step times printed here are smoke readings on a cold process, not
benchmark numbers. The last line of stdout is the JSON result; any failed
check raises, so the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

# OLMo-1B depth that fits one 16 GB v5e with >= 1.5 GB to spare: the train
# step's compiled footprint (memory_analysis, args + temps, state donated)
# is 15.36e9 B at 6 layers and 16.90e9 B at 7
ONE_CHIP_LAYERS = 6
SAVE_STEP = 4                 # steps before the save; step 5 is compared
MIN_FREE_BYTES = 1.5e9
BF16_REL_TOL = 2e-2           # loss agreement between layouts (bf16 compute)


def train_args(layers: int, steps: int, ckpt_dir: str):
    from repro.launch.train import build_argparser
    return build_argparser().parse_args(
        ["--arch", "olmo-1b", "--layers", str(layers), "--batch", "4",
         "--seq", "2048", "--steps", str(steps),
         "--ckpt-every", str(SAVE_STEP), "--ckpt-dir", ckpt_dir])


def device_info():
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def check_finite(records, what):
    for step, loss, _ in records:
        if not math.isfinite(loss):
            raise AssertionError(f"{what}: loss at step {step} is {loss}")


def bits_equal(a: np.ndarray, b: np.ndarray) -> bool:
    a, b = np.ascontiguousarray(a), np.ascontiguousarray(b)
    return (a.dtype == b.dtype and a.shape == b.shape
            and np.array_equal(a.reshape(-1).view(np.uint8),
                               b.reshape(-1).view(np.uint8)))


def bytes_in_use(devices):
    return [d.memory_stats()["bytes_in_use"] for d in devices]


def free_device(tree) -> None:
    for x in jax.tree.leaves(tree):
        if isinstance(x, jax.Array):
            x.delete()


def save_and_restore(args, cfg, opt_cfg, state, step: int):
    """TCE save of the device ``state`` at ``step`` (the engine copies it to
    the host, as ``train_span`` saves), persisted, then restored by a fresh
    engine on the same store, as ``--resume`` does. Checks every restored
    leaf bit for bit against the host copy of ``state`` and returns the
    restored TrainState of host arrays."""
    from repro.core.tce.engine import flatten_pytree
    from repro.launch.train import (PERSIST_TIMEOUT_S, open_tce,
                                    restore_state, tree_nbytes)

    nbytes = tree_nbytes(state)
    tce = open_tce(args, nbytes)
    t0 = time.perf_counter()
    h = tce.save(step, state)
    t1 = time.perf_counter()
    if not tce.reconciler.quiesce(PERSIST_TIMEOUT_S):
        raise AssertionError("checkpoint not persisted in time")
    t2 = time.perf_counter()
    tce.close()
    print(f"tce.save step={step} bytes={nbytes} save_s={t1 - t0!r} "
          f"(of it cache_wall_s={h.cache_wall_s!r}) persist_s={t2 - t1!r} "
          f"persisted", flush=True)
    del tce
    host = flatten_pytree(state)          # state is unchanged since the save

    tce = open_tce(args, nbytes)          # empty cache: reads the store
    t0 = time.perf_counter()
    try:
        got_step, restored = restore_state(tce, cfg, opt_cfg)
    finally:
        tce.close()
    print(f"tce.restore from the store restore_s={time.perf_counter() - t0!r}",
          flush=True)
    if got_step != step:
        raise AssertionError(f"restored step {got_step}, saved {step}")
    flat = flatten_pytree(restored)
    bad = [k for k in host if k not in flat or not bits_equal(host[k], flat[k])]
    if bad or set(host) != set(flat):
        raise AssertionError(f"restore differs from the save in {bad[:5]}")
    print(f"tce.restore step={got_step} leaves={len(flat)} bit-exact",
          flush=True)
    return restored


def step_loss(plan, cfg, data, state, step: int):
    from repro.launch.train import train_span
    state, rec = train_span(plan, state, data, cfg, step, step + 1)
    return state, rec[0][1]


# --------------------------------------------------------------------------- #
def one_chip(ckpt_dir: str) -> None:
    from repro.data import SyntheticLMData
    from repro.launch.train import (build_configs, make_batch, plan_steps,
                                    train_span)

    args = train_args(ONE_CHIP_LAYERS, SAVE_STEP + 1, ckpt_dir)
    cfg, opt_cfg = build_configs(args)
    print(f"config {cfg.name}: d_model={cfg.d_model} heads={cfg.n_heads}x"
          f"{cfg.d_head} d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
          f"layers={cfg.n_layers} (published 16; depth cut to fit one chip) "
          f"params={cfg.n_params()} batch={args.batch} seq={args.seq}",
          flush=True)
    plan = plan_steps(cfg, opt_cfg, args.batch, args.seq)
    data = SyntheticLMData(cfg.vocab_size, args.seq, args.batch, args.seed)
    key = jax.random.key(args.seed)

    # the compiled step must leave room on the device
    st_shapes = jax.eval_shape(plan.init, key)
    b_shapes = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                for k, v in make_batch(cfg, data, 0).items()}
    mem = plan.step.lower(st_shapes, b_shapes).compile().memory_analysis()
    need = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    print(f"step footprint {need} B of {limit} B device limit "
          f"(free {limit - need} B)", flush=True)
    if limit - need < MIN_FREE_BYTES:
        raise AssertionError(f"layers={cfg.n_layers} leaves under "
                             f"{MIN_FREE_BYTES:.0f} B free")

    # (b) train
    state = plan.init(key)
    state, records = train_span(plan, state, data, cfg, 0, SAVE_STEP)
    check_finite(records, "train")
    for step, loss, dt in records:
        print(f"smoke reading (not a benchmark): step {step} loss={loss!r} "
              f"step_s={dt!r}", flush=True)

    # (c) save, restore, resume
    restored = save_and_restore(args, cfg, opt_cfg, state, SAVE_STEP)
    state, live = step_loss(plan, cfg, data, state, SAVE_STEP)
    free_device(state)
    state, resumed = step_loss(plan, cfg, data, plan.place(restored),
                               SAVE_STEP)
    del restored
    if not (math.isfinite(live) and resumed == live):
        raise AssertionError(f"step {SAVE_STEP + 1}: live loss {live!r}, "
                             f"resumed loss {resumed!r}")
    print(f"resume: step {SAVE_STEP + 1} loss live={live!r} "
          f"resumed={resumed!r} equal", flush=True)

    # (d) int8 codec on the full-width embedding table, and on a leaf whose
    # block count is not a whole number of kernel row tiles
    codec_round_trip(np.asarray(state.params["tok"]["table"]))
    free_device(state)
    codec_round_trip(np.random.default_rng(args.seed)
                     .standard_normal(300 * 256 - 5).astype(np.float32))


def codec_round_trip(leaf: np.ndarray) -> None:
    from repro.core.tce.codec import INT8_BLOCK, decode_shard, encode_shard
    from repro.kernels.quant_blockwise.ops import quantize_blockwise

    enc, payload, meta = encode_shard(leaf, "int8")
    if enc != "int8":
        raise AssertionError(f"int8 codec fell back to {enc}")
    out = decode_shard(enc, payload, str(leaf.dtype), leaf.shape, meta)
    blocks = np.pad(leaf.reshape(-1), (0, (-leaf.size) % INT8_BLOCK))
    amax = np.abs(blocks.reshape(-1, INT8_BLOCK)).max(axis=1)
    err = np.abs(out - leaf).reshape(-1)
    err = np.pad(err, (0, blocks.size - err.size)).reshape(-1, INT8_BLOCK)
    worst = float((err.max(axis=1) / np.maximum(amax, 1e-12)).max())
    if not worst <= 0.5 / 127 * 1.01:
        raise AssertionError(f"int8 round trip error {worst} of block amax")
    lowered = quantize_blockwise.lower(
        jax.ShapeDtypeStruct(leaf.shape, np.float32), interpret=False)
    if "tpu_custom_call" not in lowered.as_text():
        raise AssertionError("quant_blockwise did not lower to a TPU kernel")
    print(f"int8 codec: leaf {leaf.shape} -> {payload.nbytes} B "
          f"(from {leaf.nbytes} B), worst error {worst!r} of block amax, "
          f"kernel compiled (tpu_custom_call)", flush=True)


# --------------------------------------------------------------------------- #
def four_chips(ckpt_dir: str) -> None:
    from repro.data import SyntheticLMData
    from repro.launch.mesh import make_mesh
    from repro.launch.train import (build_configs, plan_steps, train_span,
                                    tree_nbytes)

    devices = jax.devices()
    if len(devices) != 4:
        raise AssertionError(f"--four-chips needs 4 devices, has "
                             f"{len(devices)}")
    mesh = make_mesh((2, 2), ("data", "model"), devices=devices)
    n_cmp = 3
    args = train_args(ONE_CHIP_LAYERS, n_cmp + 1, ckpt_dir)
    cfg, opt_cfg = build_configs(args)
    data = SyntheticLMData(cfg.vocab_size, args.seq, args.batch, args.seed)
    key = jax.random.key(args.seed)
    print(f"config {cfg.name}: d_model={cfg.d_model} layers={cfg.n_layers} "
          f"batch={args.batch} seq={args.seq}; mesh data=2 model=2",
          flush=True)

    ref = plan_steps(cfg, opt_cfg, args.batch, args.seq)
    state, rec_ref = train_span(ref, ref.init(key), data, cfg, 0, n_cmp)
    free_device(state)
    check_finite(rec_ref, "devices[0]")

    plan = plan_steps(cfg, opt_cfg, args.batch, args.seq, mesh=mesh)
    state = plan.init(key)
    total = tree_nbytes(state)
    used = bytes_in_use(devices)
    print(f"state {total} B; bytes_in_use per device {used}", flush=True)
    if max(used) > 0.4 * total or min(used) < 0.15 * total:
        raise AssertionError(f"state not split over the mesh: {used}")
    state, rec = train_span(plan, state, data, cfg, 0, n_cmp)
    check_finite(rec, "mesh")
    for (s, l1, _), (_, l4, _) in zip(rec_ref, rec):
        rel = abs(l4 - l1) / abs(l1)
        print(f"step {s} loss devices[0]={l1!r} mesh={l4!r} rel={rel!r}",
              flush=True)
        if rel > BF16_REL_TOL:
            raise AssertionError(f"step {s}: mesh loss off by {rel}")

    restored = save_and_restore(args, cfg, opt_cfg, state, n_cmp)
    state, live = step_loss(plan, cfg, data, state, n_cmp)
    free_device(state)
    state, resumed = step_loss(plan, cfg, data, plan.place(restored), n_cmp)
    free_device(state)
    del restored
    if not (math.isfinite(live) and resumed == live):
        raise AssertionError(f"sharded resume: live {live!r}, "
                             f"resumed {resumed!r}")
    print(f"sharded resume: step {n_cmp + 1} loss live={live!r} "
          f"resumed={resumed!r} equal", flush=True)

    full = train_args(16, 2, ckpt_dir)
    cfg16, opt16 = build_configs(full)
    plan16 = plan_steps(cfg16, opt16, full.batch, full.seq, mesh=mesh)
    state = plan16.init(key)
    used = bytes_in_use(devices)
    state, rec16 = train_span(plan16, state, data, cfg16, 0, 2)
    check_finite(rec16, "16 layers")
    print(f"{cfg16.name} layers={cfg16.n_layers} params={cfg16.n_params()} "
          f"state {tree_nbytes(state)} B, bytes_in_use per device {used}; "
          f"losses {[r[1] for r in rec16]}", flush=True)
    free_device(state)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on a 2x2 mesh")
    args = ap.parse_args(argv)

    info = device_info()
    if info["platform"] != "tpu":
        print(f"no TPU: JAX's first device is {info['platform']!r}",
              file=sys.stderr)
        return 1
    from repro.launch.compile_cache import setup_compile_cache
    print(f"device {info}; compile cache {setup_compile_cache()}",
          flush=True)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ckpt_") as ckpt:
        if args.four_chips:
            four_chips(ckpt)
        else:
            one_chip(ckpt)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
