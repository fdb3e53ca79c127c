"""persist_s: the store showing each save's manifest minus the return of
its ``save`` call (the reconciler's digest, write and commit), mean over
the window's saves."""


def read(run):
    saves = run["saves"]
    if not saves or any(s["commit"] is None for s in saves):
        return None
    return sum(s["commit"] - s["end"] for s in saves) / len(saves)
