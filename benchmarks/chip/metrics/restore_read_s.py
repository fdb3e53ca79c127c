"""restore_read_s: wall time of ``launch/train.py:restore_state`` (the
engine's restore from the store, checksums, unflatten), mean over the
window's resumes."""


def read(run):
    rs = run["resumes"]
    return sum(r["restore_s"] for r in rs) / len(rs) if rs else None
