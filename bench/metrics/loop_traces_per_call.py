"""Traces of the solver loop's ``while_loop`` per call in the window: the
program's ``ot.loop.traces`` counter (``repro.obs``), its increments while
the profiler recorded the window. 1 means every call re-traces (and so
lowers and compiles or reads the cache); 0 that every call hit a cached
executable. None where the program has no ``ot.*`` spans."""


def read(run):
    try:
        import repro.obs as obs
    except ImportError:
        return None
    seen = obs.traced()
    if not any(k.startswith("ot.") for k in seen["spans"]) or not run.calls:
        return None
    return seen["counters"].get("ot.loop.traces", 0) / len(run.calls)
