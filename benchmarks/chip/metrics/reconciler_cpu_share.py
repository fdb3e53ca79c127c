"""reconciler_cpu_share: the reconciler thread's CPU time (its passes'
``time.thread_time``, ``tce.reconciler.cpu_s``) per save made durable in
the trace (per ``transom.persist.commit``), times the window's saves, over
the window's wall time, in %. Per commit, because the last save's persist
may still run when the trace stops."""
from chip import program


def read(run):
    prog = program.of(run)
    commits = len(prog.named("transom.persist.commit")) if prog else 0
    if not commits or not run["saves"]:
        return None
    per_save = prog.counted("tce.reconciler.cpu_s") / commits
    return 100.0 * per_save * len(run["saves"]) / run["window_s"]
