"""tokens_per_s: tokens of every step completed in the window over the
window's wall time, save stalls and batch building included."""


def read(run):
    if not run["steps"]:
        return None
    return len(run["steps"]) * run["tokens_per_step"] / run["window_s"]
