"""Host milliseconds per front-door call: the program's ``ot.*`` spans
(``repro.obs``) tallied while the profiler recorded the window, their self
times summed (the time under the outermost ones: ``ot.solve`` and
``ot.solve_many``), over the window's calls. None where the program has
no such spans."""


def read(run):
    try:
        import repro.obs as obs
    except ImportError:
        return None
    spans = {k: v for k, v in obs.traced()["spans"].items()
             if k.startswith("ot.")}
    if not spans or not run.calls:
        return None
    return 1e3 * sum(s for s, _ in spans.values()) / len(run.calls)
