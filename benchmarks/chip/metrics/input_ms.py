"""input_ms: host time of each batch the window's steps built (the token
stream's ``batch_at``, called through ``launch/train.py:make_batch``),
mean, in ms."""


def read(run):
    xs = run["input_s"]
    return 1e3 * sum(xs) / len(xs) if xs and run["steps"] else None
