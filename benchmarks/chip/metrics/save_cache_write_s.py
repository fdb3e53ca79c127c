"""save_cache_write_s: the engine's own time for writing each save into
its host cache (``SaveHandle.cache_wall_s``), mean over the window's
saves."""


def read(run):
    saves = run["saves"]
    if not saves:
        return None
    return sum(s["cache_write_s"] for s in saves) / len(saves)
