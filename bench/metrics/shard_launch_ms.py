"""Host milliseconds per call in the sharded solve's ``ot.shard`` span
(``repro.obs``): its self time, that is the checks, the padding and
placement of the fields and the dispatch of the jitted sharded program up
to the lazy result (its trace and compile on a cache miss), less the spans
nested in it, tallied while the profiler recorded the window, over the
window's calls. None where the program has no such span."""


def read(run):
    try:
        import repro.obs as obs
    except ImportError:
        return None
    spans = obs.traced()["spans"]
    if "ot.shard" not in spans or not run.calls:
        return None
    return 1e3 * spans["ot.shard"][0] / len(run.calls)
