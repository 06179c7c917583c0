"""The least time any implementation needs for a log-domain solve.

``least_seconds`` depends only on the problem's sizes, the iterations the
solve ran and the device kind, never on the plan the program chose, so no
later plan can read above 100%:

* operations: 4 (n + m) r per iteration (Algorithm 1's two two-stage
  contractions, one multiply and one add per entry of each factor) plus
  2 (n + m) r d once per call (the feature map's cross term), all at the
  chip's bf16 peak, the highest rate it has;
* bytes: the call's inputs read once (x, y, anchors, a, b, in float32) and
  its potentials written once, at the HBM peak.

A plan that recomputes features from the points, keeps them in fast memory
or fuses both half-steps into one pass over each factor does at least this
work. The bound is the larger of the two times; the chips of a sharded
solve divide it.
"""
from __future__ import annotations

import json
from pathlib import Path

__all__ = ["peaks", "operations", "bytes_moved", "least_seconds"]

_PEAKS = Path(__file__).resolve().parent / "peaks.json"
F32 = 4


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(_PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def operations(n: int, m: int, r: int, d: int, iters: int) -> float:
    return 4.0 * (n + m) * r * iters + 2.0 * (n + m) * r * d


def bytes_moved(n: int, m: int, r: int, d: int) -> float:
    inputs = (n + m) * d + r * d + n + m
    return float(F32 * (inputs + n + m))


def least_seconds(n: int, m: int, r: int, d: int, iters: int,
                  device_kind: str, chips: int = 1) -> float:
    """Lower bound on the seconds one solve of these sizes can take."""
    p = peaks(device_kind)
    t_ops = operations(n, m, r, d, iters) / p["flops_per_s"]
    t_bytes = bytes_moved(n, m, r, d) / p["hbm_bytes_per_s"]
    return max(t_ops, t_bytes) / chips
