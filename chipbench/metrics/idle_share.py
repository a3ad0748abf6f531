"""idle_share: 1 - (union of device-operation intervals / traced window), in
percent, the mean over every chip the cell holds, those given no rows too."""


def read(run):
    if run.trace is None or run.trace.busy_s is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
