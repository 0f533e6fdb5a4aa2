"""XLA compilations inside the measured window, counted by a
``jax.monitoring`` listener that the harness installs (a program read back
from the persistent cache counts too: it was not warmed up)."""


def read(run):
    return run.compiles
