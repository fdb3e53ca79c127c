"""persist_io_s: open, write, flush, fsync and rename of each leaf file
inside each persist (``transom.store.write`` held by a ``transom.persist``
span), per save persisted in the trace."""
from chip import program


def read(run):
    return program.per_persist(run, ("transom.store.write",))
