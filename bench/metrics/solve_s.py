"""Seconds per front-door call: the window's whole time over its calls."""


def read(run):
    return run.window_s / len(run.calls)
