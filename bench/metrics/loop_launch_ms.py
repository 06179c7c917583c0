"""Host milliseconds per call in the solver loop's ``ot.loop`` span: the
eager first block and the ``while_loop``'s trace, lowering, compile (or
cache read) and dispatch, tallied while the profiler recorded the window
(``repro.obs``). 0 where the loop ran only inside a cached jitted call;
None where the program has no ``ot.*`` spans."""


def read(run):
    try:
        import repro.obs as obs
    except ImportError:
        return None
    spans = obs.traced()["spans"]
    if not any(k.startswith("ot.") for k in spans) or not run.calls:
        return None
    return 1e3 * spans.get("ot.loop", (0.0, 0))[0] / len(run.calls)
