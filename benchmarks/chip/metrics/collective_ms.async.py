"""collective_ms.async: ``collective_ms`` for the TPU's async-collective
start and done fusions alone: the collectives the compiler overlaps with
compute. In ``olmo1b-16l-x4.train`` they gather the FSDP-sharded weight
slices."""
from chip import tracing


def read(run):
    tr = run["trace"]
    if tr is None or not tr.devices or not run["steps"]:
        return None
    got = tracing.collective_op_seconds(tr, tracing.step_intervals(tr),
                                        ("async-collective",))
    return None if got is None else 1e3 * got / len(run["steps"])
