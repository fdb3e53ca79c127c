"""save_stall_s: wall time of the window's ``tce.save`` calls, timed where
the loop calls them, mean per save (device-to-host copy, durability wait
and cache write: all the time the loop is blocked)."""


def read(run):
    saves = run["saves"]
    return sum(s["stall_s"] for s in saves) / len(saves) if saves else None
