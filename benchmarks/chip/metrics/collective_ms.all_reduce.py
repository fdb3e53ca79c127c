"""collective_ms.all_reduce: ``collective_ms`` for the all-reduce ops
alone. In ``olmo1b-16l-x4.train`` these are synchronous: tensor
parallelism's sums of activations over ``model``."""
from chip import tracing


def read(run):
    tr = run["trace"]
    if tr is None or not tr.devices or not run["steps"]:
        return None
    got = tracing.collective_op_seconds(tr, tracing.step_intervals(tr),
                                        ("all-reduce",))
    return None if got is None else 1e3 * got / len(run["steps"])
