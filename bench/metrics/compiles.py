"""Backend compiles inside the measured window (JAX's compile events)."""


def read(run):
    return float(run.compiles_in_window)
