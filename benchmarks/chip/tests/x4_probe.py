"""Runs at a toy size on four virtual CPU devices, in a process of its own
(``XLA_FLAGS=--xla_force_host_platform_device_count=4`` has to be set
before JAX starts): a whole run of the tiny copy of the four-chip cell
through ``harness.run``, with the bytes of the program's state that each
device holds; the same run with each fault of the step planted
(``chip_bench_tiny.faulty_plan_steps``); the same copy with TCE saves,
its sharded state saved and read back, traced, and with the saved state
altered where the save takes it from the devices; and the reference
spread over the devices in blocks of rows (the last block padded with
rows of weight 0) beside the plain one. Prints one JSON object as its last
line.

    python3 benchmarks/chip/tests/x4_probe.py
"""
import dataclasses
import json
import sys
import time

import jax
import numpy as np
from jax.sharding import Mesh

import chip_bench_tiny as tiny
from chip import harness, reference
from chip.tokens import TokenStream

SEED = 2**31 + 404
FAULTS = ("state_unchanged", "half_batch", "loss_altered", "no_exchange")


def state_bytes_per_device(tree) -> list:
    per = {}
    for x in jax.tree.leaves(tree):
        for sh in x.addressable_shards:
            per[sh.device.id] = per.get(sh.device.id, 0) + sh.data.nbytes
    return [per[k] for k in sorted(per)]


def harness_run() -> dict:
    from repro.launch import train as lt
    real = lt.plan_steps
    held = {}

    def plan_steps(*a, **kw):
        plan = real(*a, **kw)

        def init(key):
            state = plan.init(key)
            held["per_device"] = state_bytes_per_device(state)
            held["total"] = lt.tree_nbytes(state)
            return state
        return dataclasses.replace(plan, init=init)

    lt.plan_steps = plan_steps
    try:
        out = tiny.run_tiny("tiny-x4.train", seed=SEED)
    finally:
        lt.plan_steps = real
    return {"correct": out["correct"], "checks": out["checks"],
            "device": out["device"], "state": held}


def faulty_runs() -> dict:
    """correct, for a run with each fault of the step planted."""
    from repro.launch import train as lt
    real = lt.plan_steps
    out = {}
    for fault in FAULTS:
        lt.plan_steps = tiny.faulty_plan_steps(fault)
        try:
            out[fault] = tiny.run_tiny("tiny-x4.train", seed=SEED)["correct"]
        finally:
            lt.plan_steps = real
    return out


def saving_runs() -> dict:
    """The tiny copy of the four-chip cell with a TCE save every 2 steps,
    so that the short window holds one: sound; traced, with the per-layer
    metrics of the one-chip cell that saves (the per-layer metrics it
    read); and with the largest leaf of each save altered by one word as
    the engine flattens it."""
    from repro.core.tce import engine
    sound = tiny.run_tiny("tiny-x4.train", seed=SEED, save_every=2)
    bench = tiny.tiny_bench()
    cell = harness.resolve("tiny-x4.train", bench)
    cell.per_layer = harness.resolve("tiny.train_ckpt", bench).per_layer
    cell.traffic.update(save_every=2)
    traced = harness.run(cell, SEED, 0.5, True, time.perf_counter(),
                         require_tpu=False)
    real = engine.flatten_pytree
    engine.flatten_pytree = tiny.altered_saves(real)
    try:
        bad = tiny.run_tiny("tiny-x4.train", seed=SEED, save_every=2)
    finally:
        engine.flatten_pytree = real
    out = {k: {"correct": r["correct"], "checks": r["checks"],
               "count": r["device"]["count"]}
           for k, r in (("sound", sound), ("altered", bad))}
    out["traced"] = {"correct": traced["correct"],
                     "read": sorted(traced["metrics"])}
    return out


def reference_pairs() -> dict:
    config = json.loads((tiny.HERE / "tiny-x4.json").read_text())
    data = TokenStream(config["model"]["vocab_size"], config["seq"],
                       config["batch"], SEED, zipf_a=1.3, n_patterns=64,
                       noise=0.15)
    args = (config["model"], config["optimizer"], SEED, data.rows(range(3)))
    spread = dict(config["reference"]["options"], devices=jax.devices())
    shapes = jax.eval_shape(lambda: reference.init_params(
        reference.Dims.of(config["model"]), SEED))
    mesh = Mesh(np.array(jax.devices()), ("r",))
    return {"plain": reference.train(*args),
            "spread": reference.train(*args, **spread),
            "half": reference.train(*args, half_batch=True),
            "half_spread": reference.train(*args, half_batch=True, **spread),
            "placement": {
                reference.leaf_name(kp): str(reference.placement(
                    x.shape, mesh).spec)
                for kp, x in jax.tree_util.tree_flatten_with_path(shapes)[0]}}


def main() -> int:
    t0 = time.perf_counter()
    out = {"devices": len(jax.devices()), "run": harness_run(),
           "faults": faulty_runs(), "saves": saving_runs(),
           "reference": reference_pairs()}
    out["seconds"] = time.perf_counter() - t0
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
