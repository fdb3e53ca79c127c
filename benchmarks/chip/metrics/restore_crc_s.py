"""restore_crc_s: the crc32 verify of each leaf read (``transom.store.crc``
held by ``transom.restore``), per resume."""
from chip import program


def read(run):
    return program.per_restore(run, ("transom.store.crc",))
