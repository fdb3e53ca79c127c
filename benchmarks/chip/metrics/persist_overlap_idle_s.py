"""persist_overlap_idle_s: device idle time inside the window's training
steps (each ``train_span`` call less its batch build and save) while the
reconciler persists (a ``transom.persist`` span is open), mean over chips,
per save persisted in the trace."""
from chip import program, tracing


def read(run):
    tr = run["trace"]
    spans, steps = program.persists(program.of(run))
    if tr is None or not tr.devices or not steps:
        return None
    within = tracing.intersect(tracing.union(tracing.step_intervals(tr)),
                               tracing.union((s.start, s.end)
                                             for s in spans))
    return (tracing.length(within) - tracing.busy(tr, within)) / steps
