"""Reduce a profiler trace of the measured window to device metrics.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData`` reads
it. Device planes are named ``/device:<KIND>:<id>`` and hold a line of XLA
operations; host planes hold the threads' spans, among them the benchmark's
own ``bench.call`` (one front-door call) and ``bench.block`` (the wait that
ends it). Everything here works on plain tuples, so that a test can check it
on a small recorded trace and on events it writes by hand.

* window: from the first ``bench.call`` start to the last ``bench.block``
  end (the whole trace where there are none);
* busy: the union of the device's operation intervals inside the window;
* collectives: operations whose name says they exchange data between chips,
  and the part of their time with no other operation running beside them;
* idle gaps: the spans of the window in which the device ran nothing, each
  named by the innermost host span that covers its midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["Event", "TraceSummary", "summarize", "read_xplane", "op_name",
           "find_xplane", "union", "COLLECTIVE"]

CALL_SPAN = "bench.call"
BLOCK_SPAN = "bench.block"
OPS_LINES = ("XLA Ops",)
TOP = 10                 # entries of each list in a run's breakdown
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"allreduce|allgather|reducescatter|psum|send|recv", re.IGNORECASE)

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def _clip(intervals: Iterable[Interval], lo: float, hi: float):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def _subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """a minus b, both disjoint and sorted."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _leaves(events: Sequence[Event]) -> List[Event]:
    """Drop events that enclose another event of the same line (a parent op
    and its children would count twice)."""
    evs = sorted(events, key=lambda e: (e.start_ns, -e.end_ns))
    out = []
    for i, ev in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt.start_ns < ev.end_ns and \
                nxt.end_ns <= ev.end_ns and (nxt.start_ns, nxt.end_ns) != \
                (ev.start_ns, ev.end_ns):
            continue
        out.append(ev)
    return out


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    devices: int
    busy_s: float                       # mean over devices
    collective_exposed_s: Optional[float]
    device_ops: List[Tuple[str, float]]   # seconds per device, most first
    idle_gaps: List[Tuple[str, float]]    # seconds per device, most first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def summarize(device_ops: Dict[str, Sequence[Event]],
              host_spans: Sequence[Event]) -> TraceSummary:
    """Reduce per-device operation events and host spans to a summary."""
    if not device_ops:
        raise ValueError("the trace holds no device operations")
    calls = [e for e in host_spans if e.name == CALL_SPAN]
    blocks = [e for e in host_spans if e.name == BLOCK_SPAN]
    if calls and blocks:
        lo = min(e.start_ns for e in calls)
        hi = max(e.end_ns for e in blocks)
    else:
        every = [e for evs in device_ops.values() for e in evs]
        lo = min(e.start_ns for e in every)
        hi = max(e.end_ns for e in every)
    window = hi - lo
    busy_total, exposed_total, any_coll = 0.0, 0.0, False
    per_op: Dict[str, float] = defaultdict(float)
    gaps_by: Dict[str, float] = defaultdict(float)
    spans = sorted(host_spans, key=lambda e: e.start_ns)
    for dev, events in sorted(device_ops.items()):
        leaves = _leaves(events)
        ivs = list(_clip(((e.start_ns, e.end_ns) for e in leaves), lo, hi))
        busy = union(ivs)
        busy_total += _length(busy)
        for e in leaves:
            for s, t in _clip([(e.start_ns, e.end_ns)], lo, hi):
                per_op[e.name] += t - s
        coll = [(e.start_ns, e.end_ns) for e in leaves
                if COLLECTIVE.search(e.name)]
        if coll:
            any_coll = True
            compute = union(_clip(((e.start_ns, e.end_ns) for e in leaves
                                   if not COLLECTIVE.search(e.name)), lo, hi))
            exposed_total += _length(_subtract(union(_clip(coll, lo, hi)),
                                               compute))
        idle = _subtract([(lo, hi)], busy)
        for (s, t), name in zip(idle, _host_names(spans, idle)):
            gaps_by[name] += t - s
    nd = len(device_ops)
    ns = 1e-9 / nd
    ops = sorted(((k, v * ns) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:TOP]
    gaps = sorted(((k, v * ns) for k, v in gaps_by.items()),
                  key=lambda kv: -kv[1])[:TOP]
    return TraceSummary(
        window_s=window * 1e-9, devices=nd, busy_s=busy_total * ns,
        collective_exposed_s=exposed_total * ns if any_coll else None,
        device_ops=ops, idle_gaps=gaps)


def _host_names(spans: Sequence[Event],
                gaps: Sequence[Interval]) -> List[str]:
    """For each gap (sorted), the innermost host span covering its midpoint
    ("none" where no span does). ``spans`` are sorted by start."""
    names, active, i = [], [], 0
    for s, t in gaps:
        mid = 0.5 * (s + t)
        while i < len(spans) and spans[i].start_ns <= mid:
            heapq.heappush(active, (spans[i].end_ns, i))
            i += 1
        while active and active[0][0] < mid:
            heapq.heappop(active)
        if active:
            inner = min((spans[j] for _, j in active),
                        key=lambda e: e.end_ns - e.start_ns)
            names.append(inner.name)
        else:
            names.append("none")
    return names


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def op_name(hlo: str) -> str:
    """``%fusion.7 = f32[...] fusion(...)`` -> ``fusion.7``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def read_xplane(path: str):
    """(device_ops, host_spans) from an ``.xplane.pb`` file. Host spans are
    those of the thread that made the benchmark's calls (all threads' where
    no thread holds a ``bench.call`` span)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_ops: Dict[str, List[Event]] = {}
    host: List[Event] = []
    caller: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            evs = [Event(op_name(e.name), e.start_ns,
                         e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name in OPS_LINES
                   for e in line.events]
            if evs:
                device_ops[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events if e.duration_ns > 0]
                if any(e.name == CALL_SPAN for e in spans):
                    caller.extend(spans)
                host.extend(spans)
    return device_ops, caller or host
