"""Device: the share of the traced window in which no op ran, mean over
the cell's chips, in %."""


def read(run):
    if not run.trace:
        return None
    return 100 * (1 - run.trace["busy_s"] / run.trace["window_s"])
