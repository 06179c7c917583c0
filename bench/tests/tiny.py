"""A benchmark root of CPU-sized cells for the tests.

``tiny_root(tmp)`` writes ``tmp/bench`` (the real drivers, metrics and
references, with configurations and cells small enough for a CPU) and
``tmp/BENCHMARK.json``; ``run_cell`` drives ``harness.run`` on it without
the accelerator check and returns the exit code, the last line of standard
output as JSON and standard error.
"""
from __future__ import annotations

import io
import json
import shutil
from pathlib import Path

from bench import harness

REAL = Path(harness.__file__).resolve().parent

CONFIGS = {
    "higgs_tiny": dict(generator="higgs_standin", n=1024, d=28, r=128,
                       eps=0.1, method="log_factored", tol=1e-4,
                       max_iter=2000, chips=1,
                       reference="gaussian_log_sinkhorn",
                       limits={"row_err": 1.5e-4, "col_err": 2e-4}),
    "pc_tiny": dict(generator="pointcloud_standin", n=256, d=3, r=64,
                    eps=0.1, R=1.0, method="log_factored", tol=1e-4,
                    max_iter=2000, chips=1, reference="gaussian_log_sinkhorn",
                    limits={"row_err": 3e-6, "col_err": 2e-4}),
}
CELLS = {
    "higgs_tiny.solve": dict(config="higgs_tiny", traffic="solve",
                             driver="solve",
                             params={"pool_seed": 0, "instances": 2}),
    "pc_tiny.solve_many": dict(config="pc_tiny", traffic="solve_many",
                               driver="solve_many",
                               params={"pool_seed": 0, "batch": 4,
                                       "batches": 2, "keep": 4,
                                       "sample": 8}),
}


def tiny_root(tmp: Path, configs=None, cells=None) -> Path:
    """Write a benchmark root under ``tmp``; returns ``tmp/bench``."""
    configs = dict(CONFIGS if configs is None else configs)
    cells = dict(CELLS if cells is None else cells)
    root = Path(tmp) / "bench"
    for kind in ("drivers", "metrics", "references"):
        shutil.copytree(REAL / kind, root / kind,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (root / "configs").mkdir(parents=True)
    (root / "workloads").mkdir()
    for name, cfg in configs.items():
        (root / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    for name, cell in cells.items():
        (root / "workloads" / f"{name}.json").write_text(
            json.dumps(dict(cell, why="a CPU-sized cell for the tests")))
    real = json.loads((REAL.parent / "BENCHMARK.json").read_text())
    spec = dict(real, workloads=[
        dict(name=n, config=c["config"], traffic=c["traffic"],
             chips=configs[c["config"]]["chips"], why="test cell")
        for n, c in cells.items()])
    spec["per_layer"] = [dict(m, workloads=list(cells))
                         if "workloads" in m else m
                         for m in real["per_layer"]]
    (Path(tmp) / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run_cell(root: Path, cell: str, *, seed: int = 7, seconds: float = 0.5,
             trace: int = 0, call=None):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run(["--workload", cell, "--seed", str(seed), "--seconds",
                      str(seconds), "--trace", str(trace)],
                     bench_root=root, require_accelerator=False, call=call,
                     out=out, err=err)
    lines = out.getvalue().strip().splitlines()
    last = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, last, err.getvalue()
