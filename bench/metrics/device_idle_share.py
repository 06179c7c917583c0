"""Share of the traced window in which the device ran no operation (%),
averaged over the cell's chips."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
