"""collective_exposed_ms: device time in which a collective was in flight
and no other op ran on the same chip (ops that hold other ops, such as a
loop's own event, do not count as running), inside the window's training
steps, per step, mean over chips, in ms. An asynchronous collective, which
the TPU trace shows as a ``-start`` and a ``-done`` op, is in flight from
its start op's start to its done op's end
(``tracing.collective_intervals``)."""
from chip import tracing


def read(run):
    tr = run["trace"]
    if tr is None or not tr.devices or not run["steps"]:
        return None
    got = tracing.collective_exposed_seconds(tr, tracing.step_intervals(tr))
    return None if got is None else 1e3 * got / len(run["steps"])
