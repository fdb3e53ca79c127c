"""backward_ms: device self time per step of the ops whose scope path (the
trace's ``tf_op``) holds ``transpose(``: the gradient of ``loss``, full
remat's recomputed forward (``rematted_computation``) included; inside the
window's training steps, mean over chips, in ms."""
from chip import program


def read(run):
    return program.scoped_ms(run, lambda path: "transpose(" in path)
