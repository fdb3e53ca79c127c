"""save_wait_s: the pipeline-durability wait inside each save (the
program's ``transom.save.wait`` span around the reconciler's ``quiesce``),
mean per save."""
from chip import program


def read(run):
    return program.mean_seconds(run, "transom.save.wait")
