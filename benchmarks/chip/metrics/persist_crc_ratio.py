"""persist_crc_ratio: bytes passed through crc32 on the persist side
(``tce.persist.crc_bytes``: the digest and the store's payload crc) over
the payload bytes written (``tce.persist.bytes``), counted inside the
trace's ``transom.persist`` spans. 2.0 where every byte is checksummed
twice."""
from chip import program


def read(run):
    prog = program.of(run)
    spans, _ = program.persists(prog)
    if not spans:
        return None
    written = prog.counted("tce.persist.bytes", spans)
    return prog.counted("tce.persist.crc_bytes", spans) / written \
        if written else None
