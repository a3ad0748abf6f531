"""setup_s: process start to the first timed job (JAX start, Predict,
operands, plans, one warm job of every shape, compiles included)."""


def read(run):
    return run.setup_s
