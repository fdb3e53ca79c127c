"""persist_crc_s: crc32 passes on the reconciler thread inside each
persist (``transom.persist.digest`` and ``transom.store.crc`` held by a
``transom.persist`` span), per save persisted in the trace."""
from chip import program


def read(run):
    return program.per_persist(run, ("transom.persist.digest",
                                     "transom.store.crc"))
