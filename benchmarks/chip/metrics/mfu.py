"""mfu: model FLOPs of the window's steps (flops.py) over the summed wall
time of those steps (``train_span``'s block_until_ready timing) x chips x
the chip's bf16 peak (peaks.json), in %."""


def read(run):
    steps = run["steps"]
    if not steps or not run["peak_flops"]:
        return None
    busy = sum(s["dt"] for s in steps)
    return 100.0 * run["flops_per_step"] * len(steps) \
        / (busy * run["chips"] * run["peak_flops"])
