"""setup_s: process start until the window opens: imports, compile or
compile-cache load, weights from the seed, the first steps and any
warm-up save."""


def read(run):
    return run["setup_s"]
