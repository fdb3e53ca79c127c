"""compile_cache_hits: programs found in JAX's persistent compilation cache
in the window (``compile.cache_hits``), per resume. A resume that retraces
its step after a kill should find it there: at least 1."""
from chip import program


def read(run):
    return program.per_resume_count(run, "compile.cache_hits")
