"""compile_cache_misses: programs looked up in JAX's persistent compilation
cache and not found, so compiled anew, in the window
(``compile.cache_misses``), per resume."""
from chip import program


def read(run):
    return program.per_resume_count(run, "compile.cache_misses")
