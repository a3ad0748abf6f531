"""predict_s: host seconds of the Predict layer in set-up, the profiling runs
on every device and each chip's host-link measurement."""


def read(run):
    return run.predict_s
