"""restore_io_bandwidth: the bytes the restores read from the store
(``tce.restore.read_bytes``) over the time of those reads
(``transom.store.read``), both held by ``transom.restore``, in GB/s."""
from chip import program


def read(run):
    return program.bandwidth(run, "tce.restore.read_bytes",
                             "transom.store.read", "transom.restore")
