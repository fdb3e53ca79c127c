"""restore_io_s: the file reads of each restore (``transom.store.read``,
``np.fromfile`` of each leaf, held by ``transom.restore``), per resume."""
from chip import program


def read(run):
    return program.per_restore(run, ("transom.store.read",))
