"""Reduce the program's own spans in a profiler trace: time and idle device.

The program names its host spans ``ot.*`` (``repro.obs``: ``ot.solve``,
``ot.solve_many``, ``ot.stage``, ``ot.featurize``, ``ot.loop``,
``ot.finish``); the profiler writes them to the same ``.xplane.pb`` as the
device operations, on the same clock. ``program_summary`` takes what
``tracefile.read_xplane`` returns and, inside the same window as
``tracefile.summarize`` (first ``bench.call`` start to last ``bench.block``
end):

* for each span name, its seconds (a span nested in one of its own name
  counts once) and its count;
* ``idle_s``: the time inside the union of ``ot.*`` spans in which the
  device ran nothing, averaged over devices;
* ``idle_by_span``: that idle time by the innermost ``ot.*`` span at each
  gap's midpoint.
"""
from __future__ import annotations

import dataclasses
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

from bench.tracefile import (BLOCK_SPAN, CALL_SPAN, Event, _clip, _host_names,
                             _leaves, _length, _subtract, union)

__all__ = ["PREFIX", "ProgramSummary", "program_summary"]

PREFIX = "ot."


@dataclasses.dataclass
class ProgramSummary:
    window_s: float
    spans: Dict[str, Tuple[float, int]]     # name -> (seconds, count)
    idle_s: float                           # mean over devices
    idle_by_span: List[Tuple[str, float]]   # seconds per device, most first

    @property
    def idle_share(self) -> float:
        return self.idle_s / self.window_s


def _window(device_ops, host_spans) -> Tuple[float, float]:
    calls = [e.start_ns for e in host_spans if e.name == CALL_SPAN]
    blocks = [e.end_ns for e in host_spans if e.name == BLOCK_SPAN]
    if calls and blocks:
        return min(calls), max(blocks)
    every = [e for evs in device_ops.values() for e in evs]
    return min(e.start_ns for e in every), max(e.end_ns for e in every)


def _outermost(events: Sequence[Event]) -> List[Event]:
    """The events not enclosed by another of the list."""
    out: List[Event] = []
    for e in sorted(events, key=lambda e: (e.start_ns, -e.end_ns)):
        if out and e.end_ns <= out[-1].end_ns:
            continue
        out.append(e)
    return out


def program_summary(device_ops: Dict[str, Sequence[Event]],
                    host_spans: Sequence[Event]) -> ProgramSummary:
    """Reduce the ``ot.*`` host spans and the device operations."""
    if not device_ops:
        raise ValueError("the trace holds no device operations")
    lo, hi = _window(device_ops, host_spans)
    mine = [e for e in host_spans if e.name.startswith(PREFIX)]
    spans = {}
    for name in sorted({e.name for e in mine}):
        same = [e for e in mine if e.name == name]
        ivs = union(_clip(((e.start_ns, e.end_ns) for e in same), lo, hi))
        inside = [e for e in _outermost(same)
                  if min(e.end_ns, hi) > max(e.start_ns, lo)]
        if inside:
            spans[name] = (_length(ivs) * 1e-9, len(inside))
    program = union(_clip(((e.start_ns, e.end_ns) for e in mine), lo, hi))
    ordered = sorted(mine, key=lambda e: e.start_ns)
    idle_total = 0.0
    by_span: Dict[str, float] = defaultdict(float)
    for events in device_ops.values():
        busy = union(_clip(((e.start_ns, e.end_ns) for e in _leaves(events)),
                           lo, hi))
        gaps = _subtract(program, busy)
        idle_total += _length(gaps)
        for (s, t), name in zip(gaps, _host_names(ordered, gaps)):
            by_span[name] += t - s
    ns = 1e-9 / len(device_ops)
    return ProgramSummary(
        window_s=(hi - lo) * 1e-9, spans=spans, idle_s=idle_total * ns,
        idle_by_span=sorted(((k, v * ns) for k, v in by_span.items()),
                            key=lambda kv: -kv[1]))
