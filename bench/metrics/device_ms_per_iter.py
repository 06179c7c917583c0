"""Device busy milliseconds per solver-loop trip, from the trace."""


def read(run):
    if run.trace is None:
        return None
    trips = sum(c.iters for c in run.calls)
    return 1e3 * run.trace.busy_s / trips if trips else None
