"""save_copy_share: the bytes the window's saves copied into the host
cache's slabs (``tce.save.copied_bytes``) over the bytes they pulled from
the device (``tce.save.d2h_bytes``), in %. 0 where the cache adopts every
device-to-host buffer; nothing where the program does not count its
copies."""
from chip import program

COPIED = "tce.save.copied_bytes"


def read(run):
    prog = program.of(run)
    if prog is None or not any(c.meta.get("counter") == COPIED
                               for c in prog.counts):
        return None
    d2h = prog.counted("tce.save.d2h_bytes")
    return 100.0 * prog.counted(COPIED) / d2h if d2h else None
