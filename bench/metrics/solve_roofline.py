"""Share of the device's busy time that the least possible work needs (%).

The least time is ``bench.roofline.least_seconds`` summed over every problem
the window's calls solved: a function of sizes, iterations and device kind
alone, so it cannot pass 100%.
"""
from bench.roofline import least_seconds


def read(run):
    if run.trace is None or run.trace.busy_s <= 0:
        return None
    least = sum(least_seconds(n, m, r, d, it, run.device_kind, run.chips)
                for call in run.calls for n, m, r, d, it in call.problems)
    return 100.0 * least / run.trace.busy_s
