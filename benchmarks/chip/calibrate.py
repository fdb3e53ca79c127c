#!/usr/bin/env python3
"""Readings that a training configuration's limits are set from, in one
process (the benchmark's own runs never run this).

    python3 benchmarks/chip/calibrate.py --config olmo1b-6l \
        --seeds 11,12,... --control-seeds 11,12,13 --out calib.json

The configuration's ``layout`` and ``reference`` keys apply as in a run
(``harness``): the program is planned over the layout's chips, and the
configuration's reference module runs with its options over them. For each
of ``--seeds``: the program's first steps, driven as a run drives them,
against the float32 reference: the three numbers of
``harness.compare`` (the lower readings). For each of ``--control-seeds``,
the same numbers for the controls and a planted fault, each against the
float32 reference of that seed: the reference with its state and products
in bfloat16 (one step below the configuration's float32 state), with its
products' inputs rounded to float8 e4m3 (one step below the bfloat16
compute), and over half of the batch. ``--resume-control-seeds`` runs a
resume cell with the program's int8 checkpoint codec switched on, and
reports its exact comparisons.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE.parent))

CONTROLS = {
    "bf16_state": {"state_dtype": "bfloat16", "compute_dtype": "bfloat16"},
    "fp8_compute": {"compute_dtype": "float8_e4m3fn"},
    "half_batch": {"half_batch": True},
}


def seeds_arg(s: str):
    return [int(x) for x in s.split(",") if x]


def training_readings(config: dict, traffic: dict, seeds, control_seeds,
                      log=print, devices=None) -> dict:
    import jax
    import jax.numpy as jnp

    from chip import harness, reference
    from chip.tokens import TokenStream
    from repro.launch import train as lt

    devices = devices or jax.devices()[:1]
    cfg, opt_cfg = harness.build_model(config)
    batch, seq, n = config["batch"], config["seq"], traffic["check_steps"]
    plan = harness.plan_of(lt, config, cfg, opt_cfg, devices)
    ref, options, _ = harness.reference_of(config)
    sq_norms = jax.jit(reference.slice_sq_norms)
    out = {"program": {}, "controls": {k: {} for k in CONTROLS}}
    refs = {}

    def ref_of(seed, data, **kw):
        return ref.train(config["model"], config["optimizer"], seed,
                         data.rows(range(n)), devices=devices, **options,
                         **kw)

    for seed in seeds:
        t0 = time.perf_counter()
        data = TokenStream.from_traffic(traffic, cfg.vocab_size, seq, batch,
                                        seed)
        state = plan.init(jax.random.key(seed))
        state, readings = harness.first_steps(lt, plan, cfg, data, state, n,
                                              sq_norms, opt_cfg.b1)
        harness.free(state)
        del state
        prog = harness.program_numbers(readings)
        refs[seed] = ref_of(seed, data)
        out["program"][seed] = harness.compare(prog, refs[seed])
        log(f"program seed={seed} {out['program'][seed]} "
            f"({time.perf_counter() - t0:.1f}s)")
    for seed in control_seeds:
        data = TokenStream.from_traffic(traffic, cfg.vocab_size, seq, batch,
                                        seed)
        if seed not in refs:
            refs[seed] = ref_of(seed, data)
        for name, kw in CONTROLS.items():
            t0 = time.perf_counter()
            kw = {k: (getattr(jnp, v) if k.endswith("dtype") else v)
                  for k, v in kw.items()}
            out["controls"][name][seed] = harness.compare(
                ref_of(seed, data, **kw), refs[seed])
            log(f"{name} seed={seed} {out['controls'][name][seed]} "
                f"({time.perf_counter() - t0:.1f}s)")
    return out


def resume_control(cell_name: str, seeds, log=print) -> dict:
    """The resume cell with the int8 codec on: its exact comparisons."""
    from chip import harness
    cell = harness.resolve(cell_name)
    cell.traffic["codec"] = "int8"
    out = {}
    for seed in seeds:
        res = harness.run(cell, seed, 1.0, False, time.perf_counter())
        out[seed] = {k: v["value"] for k, v in res["checks"].items()}
        log(f"int8 resume seed={seed} {out[seed]} correct={res['correct']}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", default="train")
    ap.add_argument("--seeds", type=seeds_arg, default=[])
    ap.add_argument("--control-seeds", type=seeds_arg, default=[])
    ap.add_argument("--resume-cell", default="")
    ap.add_argument("--resume-control-seeds", type=seeds_arg, default=[])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import contextlib

    from chip import harness
    bench = harness.load_benchmark()
    conf = {c["name"]: c for c in bench["configs"]}[args.config]
    config = json.loads((harness.ROOT / conf["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{args.traffic}.json")
                         .read_text())
    devices = harness.devices_for(harness.layout_chips(config))
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = {"config": args.config, "device": devices[0].device_kind,
              "chips": len(devices), "layout": config.get("layout")}
    with contextlib.redirect_stdout(sys.stderr):
        if args.seeds or args.control_seeds:
            result["training"] = training_readings(
                config, traffic, args.seeds, args.control_seeds, log,
                devices)
        if args.resume_control_seeds:
            result["resume_int8"] = resume_control(
                args.resume_cell, args.resume_control_seeds, log)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(result, indent=1))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
