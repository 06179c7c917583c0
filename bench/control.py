"""Readings that set a cell's limit: the program's, and the control's.

    python3 bench/control.py --workload <cell> --which program|control \
        --seconds <s> --seeds <n> [<n> ...]

Runs the cell once per seed in this one process, as ``run.py`` does, and
prints one JSON line per seed with the numbers the check compared. With
``--which control`` the plain reference solver takes the program's place in
the window, its features' cross term computed at ``bf16_3x`` (three bfloat16
products, the precision next below the configuration's float32 at
HIGHEST); every such run has to come out not correct. The benchmark's own
runs never run this.
"""
from __future__ import annotations

import argparse
import io
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from bench import harness  # noqa: E402


def control_step(bench_root=harness.BENCH, cell: str = None):
    """A ``call`` for ``harness.run``: the reference solver at bf16_3x."""
    c = harness.load_cell(cell, bench_root)
    ref = harness.load_module(bench_root, "references", c.config["reference"])
    return lambda drv, i: drv.control_call(i, ref, ref.BF16_3X)


def readings(cell: str, which: str, seeds, seconds: float, *,
             bench_root=harness.BENCH, require_accelerator=True):
    """Run the cell once per seed; yields one dict of readings per seed."""
    call = control_step(bench_root, cell) if which == "control" else None
    for seed in seeds:
        out, err = io.StringIO(), io.StringIO()
        rc = harness.run(["--workload", cell, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", "0"],
                         bench_root=bench_root, call=call, out=out, err=err,
                         require_accelerator=require_accelerator)
        lines = out.getvalue().strip().splitlines()
        if rc != 0 or not lines:
            yield dict(seed=seed, which=which, rc=rc, error=err.getvalue())
            continue
        window, last = json.loads(lines[-2]), json.loads(lines[-1])
        yield dict(seed=seed, which=which, correct=last["correct"],
                   checks=last["checks"], calls=window["calls"],
                   iters=window["iters"], parts=window["parts"],
                   metrics=last["metrics"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--which", choices=("program", "control"),
                    required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for line in readings(args.workload, args.which, args.seeds, args.seconds):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
