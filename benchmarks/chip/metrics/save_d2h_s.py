"""save_d2h_s: the device-to-host copy inside each save (the program's
``transom.save.d2h`` span around ``flatten_pytree``), mean per save."""
from chip import program


def read(run):
    return program.mean_seconds(run, "transom.save.d2h")
