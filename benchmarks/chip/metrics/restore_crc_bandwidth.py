"""restore_crc_bandwidth: the bytes the restores verify
(``tce.restore.crc_bytes``) over the time of those crcs
(``transom.store.crc``), both held by ``transom.restore``, in GB/s."""
from chip import program


def read(run):
    return program.bandwidth(run, "tce.restore.crc_bytes",
                             "transom.store.crc", "transom.restore")
