"""A reference module of its own, for the test that a configuration's
``reference`` key picks the module the harness calls: the plain reference,
with each call recorded."""
from chip import flops, reference

CALLS = []


def train(*args, **kwargs):
    CALLS.append(("train", kwargs))
    return reference.train(*args, **kwargs)


def train_step_flops(model, batch, seq):
    CALLS.append(("flops", batch, seq))
    return flops.train_step_flops(model, batch, seq)
