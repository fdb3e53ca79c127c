"""resume_s: from the kill (device state deleted, engine closed, JAX's
in-memory caches cleared) until the first step after the resume is done;
mean over the resumes of the window."""


def read(run):
    rs = run["resumes"]
    return sum(r["total_s"] for r in rs) / len(rs) if rs else None
