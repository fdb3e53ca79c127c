"""step_device_ms: device busy time inside the window's training steps
(each ``train_span`` call less its batch build and save), per step, mean
over chips, in ms. From the trace."""
from chip import tracing


def read(run):
    tr = run["trace"]
    if tr is None or not tr.devices or not run["steps"]:
        return None
    return 1e3 * tracing.busy(tr, tracing.step_intervals(tr)) \
        / len(run["steps"])
