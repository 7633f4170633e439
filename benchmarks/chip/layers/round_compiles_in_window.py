"""XLA compiles inside the measured window, counted from JAX's compile
log; set-up warms every shape, so it should read 0."""


def read(run):
    return float(run.compiles_in_window)
