"""Backend compiles inside the measured window (JAX monitoring events)."""


def read(run):
    return run.window_compiles
