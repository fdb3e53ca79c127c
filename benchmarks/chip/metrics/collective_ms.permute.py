"""collective_ms.permute: ``collective_ms`` for the collective-permute ops
alone (start, done and synchronous)."""
from chip import tracing


def read(run):
    tr = run["trace"]
    if tr is None or not tr.devices or not run["steps"]:
        return None
    got = tracing.collective_op_seconds(tr, tracing.step_intervals(tr),
                                        ("collective-permute",))
    return None if got is None else 1e3 * got / len(run["steps"])
