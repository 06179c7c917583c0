"""Spans, counters and the plan-selection hook: the program's own names.

``span(name, **stats)`` is a ``jax.profiler.TraceAnnotation``: while a
profiler session records (``jax.profiler.trace(dir)``), it lands in the
same ``.xplane.pb`` as the device operations, on the same clock, with
``stats`` as event stats; otherwise it costs about a microsecond. While a
session records, each span's host time is also tallied here by name, as
self time (its time less that of the spans nested in it on its thread),
so the tallies of all names add up to the time under the outermost spans.

``count(name, n)`` adds to a process-wide counter; ``counters()`` copies
them. Increments made while a profiler session records are tallied too.
``traced()`` returns both tallies: what the profiled windows of this
process saw, without reading the trace.

The solvers name these spans and counters (see README, "Tracing"):
``ot.solve`` (with ``call``), ``ot.solve_many``, ``ot.stage``,
``ot.featurize``, ``ot.loop``, ``ot.finish``; ``ot.solve.calls`` and
``ot.loop.traces`` (one per trace of a solver loop).

``observe_plan_selection`` collects the fused-plan selections the solvers
report through ``notify_plan_selected`` (at trace time).
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, List, Tuple

from jax.profiler import TraceAnnotation

__all__ = ["span", "count", "counters", "traced", "notify_plan_selected",
           "observe_plan_selection"]

_lock = threading.Lock()
_counters: Dict[str, int] = {}
_traced_counts: Dict[str, int] = {}
_traced_spans: Dict[str, Tuple[float, int]] = {}   # name -> (self s, count)
_open = threading.local()                          # .stack of open frames


class span:
    """``with span("ot.loop"):`` -- a profiler span, tallied while traced."""

    __slots__ = ("_name", "_annotation", "_frame")

    def __init__(self, name: str, **stats):
        self._name = name
        self._annotation = TraceAnnotation(name, **stats)
        self._frame = None

    def __enter__(self):
        self._annotation.__enter__()
        if TraceAnnotation.is_enabled():
            stack = getattr(_open, "stack", None)
            if stack is None:
                stack = _open.stack = []
            # [start, seconds of the spans nested in this one]
            self._frame = [time.perf_counter(), 0.0]
            stack.append(self._frame)
        return self

    def __exit__(self, *exc):
        frame, self._frame = self._frame, None
        if frame is not None:
            took = time.perf_counter() - frame[0]
            stack = _open.stack
            stack.pop()                 # spans nest: this one is innermost
            if stack:
                stack[-1][1] += took
            with _lock:
                s, c = _traced_spans.get(self._name, (0.0, 0))
                _traced_spans[self._name] = (s + took - frame[1], c + 1)
        self._annotation.__exit__(*exc)
        return False


def count(name: str, n: int = 1) -> int:
    """Add ``n`` to counter ``name``; returns its new value."""
    traced_now = TraceAnnotation.is_enabled()
    with _lock:
        total = _counters[name] = _counters.get(name, 0) + n
        if traced_now:
            _traced_counts[name] = _traced_counts.get(name, 0) + n
    return total


def counters() -> Dict[str, int]:
    """A copy of every counter, as counted since the process started."""
    with _lock:
        return dict(_counters)


def traced() -> dict:
    """What was recorded while a profiler session ran, in this process:
    ``spans`` maps a span name to (self seconds, count), ``counters`` a
    counter name to its increments."""
    with _lock:
        return dict(spans=dict(_traced_spans), counters=dict(_traced_counts))


# ---------------------------------------------------------------------------
# Plan-selection hook
# ---------------------------------------------------------------------------

_PLAN_OBSERVERS: List[Callable[[dict], None]] = []


def notify_plan_selected(event: dict) -> None:
    """Called by the solvers when a fused plan is installed on a hot loop.

    Fires at TRACE time (plan selection is a Python-level decision), so a
    jitted solve notifies on its first call per compilation."""
    for cb in list(_PLAN_OBSERVERS):
        cb(dict(event))


@contextlib.contextmanager
def observe_plan_selection():
    """Collect plan-selection events: ``with observe_plan_selection() as ev:
    solve(...)`` then assert on ``ev`` (list of dicts with ``geometry`` /
    ``mode`` / ``kind`` / ``precision`` / ``interpret`` keys, and ``step``:
    "megakernel" for the persistent block step, "per_iteration" for the
    streaming plan)."""
    events: List[dict] = []
    _PLAN_OBSERVERS.append(events.append)
    try:
        yield events
    finally:
        _PLAN_OBSERVERS.remove(events.append)
