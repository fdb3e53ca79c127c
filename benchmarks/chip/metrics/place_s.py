"""place_s: ``StepPlan.place`` of the restored host state followed by
block_until_ready (host to device), mean over the window's resumes."""


def read(run):
    rs = run["resumes"]
    return sum(r["place_s"] for r in rs) / len(rs) if rs else None
