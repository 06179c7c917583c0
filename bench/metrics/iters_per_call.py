"""Solver-loop trips per call: the mean over the window's calls of the
highest lane's ``n_iter`` (the trips a vmapped ``while_loop`` ran)."""


def read(run):
    return sum(c.iters for c in run.calls) / len(run.calls)
