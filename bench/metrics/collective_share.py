"""Share of the traced window in which collectives ran with no other
operation beside them on the device (%), averaged over the cell's chips."""


def read(run):
    if run.trace is None or run.trace.collective_exposed_s is None:
        return None
    return 100.0 * run.trace.collective_exposed_s / run.trace.window_s
