"""optimizer_ms: device self time per step of the ops whose scope path (the
trace's ``tf_op``) holds ``optimizer`` (``jax.named_scope`` around
``adam_update``: clipping, moments, weight update), inside the window's
training steps, mean over chips, in ms."""
from chip import program


def read(run):
    return program.scoped_ms(run, lambda path: "optimizer" in path)
