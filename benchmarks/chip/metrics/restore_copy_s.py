"""restore_copy_s: reassembling the restored leaves (``unshard_state``,
``transom.restore.unshard``) and shaping them as the train state
(``unflatten_like`` in ``restore_state``, ``transom.restore.unflatten``),
per resume."""
from chip import program


def read(run):
    return program.per_restore(run, ("transom.restore.unshard",
                                     "transom.restore.unflatten"),
                               inner=False)
