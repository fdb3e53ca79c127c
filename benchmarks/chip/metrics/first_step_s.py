"""first_step_s: the first step after a resume, through ``train_span``:
batch, retrace, the step program fetched from the persistent compile
cache, and the step run to completion. Mean over the window's resumes."""


def read(run):
    rs = run["resumes"]
    return sum(r["first_step_s"] for r in rs) / len(rs) if rs else None
