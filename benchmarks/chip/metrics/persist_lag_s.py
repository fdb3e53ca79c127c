"""persist_lag_s: for each save started in the window, the host-clock time
at which the store first shows its manifest (durable) minus the start of
its ``save`` call; mean over saves."""


def read(run):
    saves = run["saves"]
    if not saves or any(s["commit"] is None for s in saves):
        return None
    return sum(s["commit"] - s["start"] for s in saves) / len(saves)
