"""collective_ms: device time of the collective ops themselves (all-reduce,
all-gather, reduce-scatter, collective-permute, all-to-all and the TPU's
async-collective fusions): each synchronous op, and each ``-start`` and
``-done`` op of an asynchronous one, not the time between them, inside the
window's training steps (each ``train_span`` call less its batch build),
per step, mean over chips, in ms."""
from chip import tracing


def read(run):
    tr = run["trace"]
    if tr is None or not tr.devices or not run["steps"]:
        return None
    got = tracing.collective_op_seconds(tr, tracing.step_intervals(tr))
    return None if got is None else 1e3 * got / len(run["steps"])
