"""idle_share.train: 1 - (union of the device's busy intervals / the
traced window) in a training window, mean over chips, in %."""
from chip import tracing


def read(run):
    tr = run["trace"]
    if tr is None or not tr.devices or not run["steps"]:
        return None
    win = tr.span("bench.window")
    return 100.0 * (1.0 - tracing.busy(tr, [win]) / (win[1] - win[0]))
