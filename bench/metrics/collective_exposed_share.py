"""Collectives (`distributed.collectives` ring ppermute): the share of
the traced window in which a collective ran on a device and no other op
did, mean over the devices, in %."""


def read(run):
    if not run.trace or run.trace["collective_s"] == 0:
        return None
    return 100 * run.trace["collective_exposed_s"] / run.trace["window_s"]
