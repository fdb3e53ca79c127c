"""The benchmark's harness at a toy size on the CPU: a BENCHMARK.json with
the test configurations' cells added, and a run that skips the look for a
chip."""
import copy
import dataclasses
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
sys.path.insert(0, str(CHIP.parents[1] / "src"))
sys.path.insert(0, str(CHIP.parent))

from chip import harness  # noqa: E402

TINY = {"olmo1b-6l": "tiny", "olmo1b-16l-x4": "tiny-x4"}


def toy(cell: str) -> str:
    config, mix = cell.split(".", 1)
    return f"{TINY[config]}.{mix}"


def tiny_bench() -> dict:
    """The real BENCHMARK.json plus a toy copy of each cell."""
    bench = harness.load_benchmark()
    out = copy.deepcopy(bench)
    for name in TINY.values():
        out["configs"].append({"name": name,
                               "file": str(HERE / f"{name}.json")})
    for w in bench["workloads"]:
        out["workloads"].append(dict(w, name=toy(w["name"]),
                                     config=TINY[w["config"]]))
    for m in out["end_to_end"] + out["per_layer"]:
        if "workloads" in m:
            m["workloads"] = m["workloads"] + [toy(w) for w in m["workloads"]]
    return out


def run_tiny(cell: str, seed: int = 2**31 + 77, seconds: float = 0.5,
             trace: bool = False, **traffic) -> dict:
    c = harness.resolve(cell, tiny_bench())
    c.traffic.update(traffic)
    return harness.run(c, seed, seconds, trace, time.perf_counter(),
                       require_tpu=False)


def altered_saves(flatten):
    """``engine.flatten_pytree`` with one word of the largest leaf of each
    save altered: a checkpoint altered where the save takes it."""
    import numpy as np

    def altered(tree):
        flat = flatten(tree)
        key = max(flat, key=lambda k: flat[k].size)
        leaf = np.array(flat[key])
        leaf.reshape(-1)[0] += 1
        flat[key] = leaf
        return flat
    return altered


def faulty_plan_steps(fault: str):
    """A ``plan_steps`` whose step is broken: ``state_unchanged`` (the step
    returns its state as it got it), ``half_batch`` (the loss over the
    first half of the rows), ``loss_altered`` (the loss it reports, 0.1%
    off), ``no_exchange`` (over a mesh: each data replica steps on its own
    rows with no exchange between chips, and the first replica's state goes
    on)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.launch import train as lt
    from repro.train import TrainConfig, make_train_step
    real = lt.plan_steps

    def plan_steps(cfg, opt_cfg, batch, seq, **kw):
        plan = real(cfg, opt_cfg, batch, seq, **kw)
        core = make_train_step(cfg, opt_cfg, TrainConfig())
        if fault == "state_unchanged":
            step = jax.jit(lambda s, b: (s, core(s, b)[1]))
        elif fault == "half_batch":
            step = jax.jit(lambda s, b: core(
                s, {k: v[: v.shape[0] // 2] for k, v in b.items()}),
                donate_argnums=(0,))
        elif fault == "no_exchange":
            step = jax.jit(jax.shard_map(
                core, mesh=kw["mesh"], in_specs=(P(), P("data")),
                out_specs=(P(), P()), check_vma=False), donate_argnums=(0,))
        else:                                    # the loss altered
            def altered(s, b):
                s, m = core(s, b)
                return s, dict(m, loss=m["loss"] * 1.001)
            step = jax.jit(altered, donate_argnums=(0,))
        return dataclasses.replace(plan, step=step)

    return plan_steps
