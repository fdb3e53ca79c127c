"""compile_s: JAX's compile work in the window (``compile.seconds``:
tracing, lowering, and the backend compile with its persistent-cache
read, each nested event once), per resume."""
from chip import program


def read(run):
    return program.per_resume_count(run, "compile.seconds")
