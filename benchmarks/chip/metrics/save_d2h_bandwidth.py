"""save_d2h_bandwidth: the bytes each save copies from the device
(``tce.save.d2h_bytes``) over the time of that copy (``transom.save.d2h``),
in GB/s."""
from chip import program


def read(run):
    return program.bandwidth(run, "tce.save.d2h_bytes", "transom.save.d2h")
