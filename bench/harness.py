"""One run of one benchmark cell: set-up, the measured window, the check.

``run`` is what ``bench/run.py`` calls. It finds everything by name:

* ``BENCHMARK.json`` (at the checkout's root): the cell's entry and the
  metrics that it reports;
* ``workloads/<cell>.json``: the configuration, the driver and its
  parameters;
* ``configs/<config>.json``: the sizes, the reference and the check's limits;
* ``drivers/<driver>.py``: a ``Driver`` class (see ``ClosedLoopDriver``);
* ``metrics/<metric>.py``: a ``read(run) -> float | None`` function;
* ``references/<reference>.py``: the plain reference the answers are read
  against.

A later cell, configuration or metric is one more file and one more entry.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence

BENCH = Path(__file__).resolve().parent

__all__ = ["Cell", "CallRecord", "Problem", "Answer", "Run", "ClosedLoopDriver",
           "load_cell", "load_module", "run", "main"]


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def _read_json(path: Path) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(bench_root: Path, kind: str, name: str):
    """Import ``<bench_root>/<kind>/<name>.py`` under a name of its own."""
    path = Path(bench_root) / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} {name!r}: {path} is missing")
    spec = importlib.util.spec_from_file_location(
        f"_bench_{kind}_{name}_{abs(hash(str(path)))}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    workload: dict          # workloads/<cell>.json
    config: dict            # configs/<config>.json
    end_to_end: List[dict]  # the BENCHMARK.json metrics this cell reports
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_root: Path = BENCH) -> Cell:
    """The cell ``name`` as BENCHMARK.json (beside ``bench_root``) and its
    own files describe it."""
    bench_root = Path(bench_root)
    benchmark = bench_root.parent / "BENCHMARK.json"
    spec = _read_json(benchmark)
    entries = [w for w in spec["workloads"] if w["name"] == name]
    if not entries:
        raise KeyError(f"no workload {name!r} in {benchmark}")
    entry = entries[0]
    workload = _read_json(bench_root / "workloads" / f"{name}.json")
    for key in ("config", "traffic"):
        if workload[key] != entry[key]:
            raise ValueError(f"{name}: workloads/{name}.json says {key} "
                             f"{workload[key]!r}, BENCHMARK.json "
                             f"{entry[key]!r}")
    config = _read_json(bench_root / "configs" / f"{workload['config']}.json")
    return Cell(name=name, chips=int(entry["chips"]), workload=workload,
                config=config,
                end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


# ---------------------------------------------------------------------------
# What a run records
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class CallRecord:
    """One front-door call of the window, read back after it ended."""
    failed: bool
    iters: int                  # loop trips: the highest lane's n_iter
    problems: List[tuple]       # (n, m, r, d, n_iter) of each problem solved


class Problem(NamedTuple):
    """One point-cloud problem as the reference reads it (uniform weights)."""
    x: Any
    y: Any
    anchors: Any
    eps: float
    R: float


@dataclasses.dataclass
class Answer:
    """One returned answer, kept for the check after the window."""
    problem: Any                # what the reference needs (driver's own)
    f: Any
    g: Any
    cost: Any
    n_iter: int


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    device_kind: str
    chips: int
    setup_s: float
    window_s: float             # host clock, first call start to last end
    calls: List[CallRecord]
    window_compiles: int
    peak_bytes: int
    trace: Any = None           # tracefile.TraceSummary, with --trace 1


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------


class ClosedLoopDriver:
    """One front-door call at a time. A subclass makes its data on the
    device in ``__init__`` and implements ``call``, ``account``, ``keep``
    and ``control_call``; the harness drives it."""

    def __init__(self, config: dict, params: dict, seed: int, devices):
        self.config, self.params, self.seed = config, params, seed
        self.devices = devices

    def warmup(self) -> None:
        """Run every shape the window will use (one call each)."""
        import jax
        self.account(jax.block_until_ready(self.call(0)))

    def call(self, i: int):
        raise NotImplementedError

    def account(self, out) -> CallRecord:
        raise NotImplementedError

    def keep(self, i: int, out, record: CallRecord) -> List[Answer]:
        """The answers of call ``i`` to keep for the check."""
        raise NotImplementedError

    def sample(self, kept: List[Answer], rng) -> List[Answer]:
        """The answers the check reads: all of them, or ``params["sample"]``
        of them drawn with ``rng`` (a generator seeded from the run's seed)."""
        k = self.params.get("sample")
        if k is None or k >= len(kept):
            return kept
        return [kept[j] for j in sorted(rng.choice(len(kept), size=k,
                                                   replace=False))]

    def control_call(self, i: int, reference, precision):
        """Call ``i`` with the plain reference solver in the program's place."""
        raise NotImplementedError

    def release(self) -> None:
        """Drop what the program built; the reference's inputs stay."""


def failed(cost: float, err: float, tol: float) -> bool:
    """A solve fails if its cost is not finite or it stopped above tol."""
    return not math.isfinite(cost) or not (err <= tol)


# ---------------------------------------------------------------------------
# The run
# ---------------------------------------------------------------------------


def _args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _finite(v: float) -> float:
    """JSON has no infinity: an unreadable number reads as the largest float."""
    return v if math.isfinite(v) else sys.float_info.max


def _device_info(devices) -> dict:
    d0 = devices[0]
    return dict(platform=d0.platform, kind=d0.device_kind, count=len(devices))


def check_answers(cell: Cell, answers: Sequence[Answer],
                  reference) -> Dict[str, dict]:
    """Read each answer against the plain reference.

    Every number the configuration gives a limit for is compared, the worst
    answer's reading against that limit; the reference's other readings are
    returned under ``_parts`` for the record. No answer reads as infinity.
    """
    limits = {k: float(v) for k, v in cell.config["limits"].items()}
    worst: Dict[str, float] = {}
    for ans in answers:
        p = ans.problem
        got = reference.check(p.x, p.y, p.anchors, ans.f, ans.g, ans.cost,
                              eps=p.eps, R=p.R)
        for k, v in got.items():
            v = float(v) if math.isfinite(v) else math.inf
            worst[k] = max(worst.get(k, -math.inf), v)
    checks = {k: dict(value=_finite(worst.get(k, math.inf)), limit=lim)
              for k, lim in limits.items()}
    parts = {k: _finite(v) for k, v in worst.items() if k not in limits}
    checks["_parts"] = dict(parts, answers=len(answers))
    return checks


def run(argv: Optional[Sequence[str]] = None, *, t0: Optional[float] = None,
        bench_root: Path = BENCH, require_accelerator: bool = True,
        call: Optional[Callable[[Any, int], Any]] = None,
        out=None, err=None) -> int:
    """Run one cell once and print its result line. Returns the exit code.

    ``call(driver, i)``, where given, takes the place of ``driver.call(i)``
    in the window (the control and the tests' planted faults use it).
    """
    t0 = time.perf_counter() if t0 is None else t0
    out = out or sys.stdout
    err = err or sys.stderr
    args = _args(argv)
    cell = load_cell(args.workload, bench_root)

    import jax
    import numpy as np
    devices = jax.devices()
    if require_accelerator:
        if devices[0].platform != "tpu":
            print(f"bench: no TPU: JAX's first device is "
                  f"{devices[0].platform!r}", file=err)
            return 2
        if len(devices) < cell.chips:
            print(f"bench: {args.workload} needs {cell.chips} chips, JAX "
                  f"finds {len(devices)}", file=err)
            return 2
    devices = devices[:cell.chips]

    from bench import tracefile
    from bench.meter import CompileMeter, peak_bytes
    from repro.kernels.ops import observe_plan_selection
    from repro.launch.compile_cache import enable_compile_cache

    # off the chip (the tests) nothing is written to the persistent cache
    cache_dir = enable_compile_cache() if require_accelerator else None
    meter = CompileMeter()
    wl = cell.workload
    driver_mod = load_module(bench_root, "drivers", wl["driver"])
    reference = load_module(bench_root, "references",
                            cell.config["reference"])
    driver = driver_mod.Driver(cell.config, wl.get("params", {}), args.seed,
                               devices)
    with observe_plan_selection() as events:
        driver.warmup()
    setup_compiles = meter.since((0, 0.0, 0))
    plans = sorted({f"{e['mode']}/{e['kind']}/{e['step']}"
                    f"/interpret={e['interpret']}" for e in events})
    print(json.dumps(dict(cell=cell.name, seed=args.seed, plans=plans,
                          compile_cache=cache_dir,
                          setup_compiles=setup_compiles["compiles"],
                          setup_compile_s=setup_compiles["compile_s"],
                          setup_cache_hits=setup_compiles["cache_hits"])),
          file=out, flush=True)

    step = call or (lambda drv, i: drv.call(i))
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace else None
    try:
        if trace_dir:
            # no Python tracer: it slows every Python call of the host
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        mark = meter.mark()
        records: List[CallRecord] = []
        kept: List[Answer] = []
        t_first = time.perf_counter()
        i = 0
        while True:
            with jax.profiler.TraceAnnotation(tracefile.CALL_SPAN):
                res = step(driver, i)
            with jax.profiler.TraceAnnotation(tracefile.BLOCK_SPAN):
                res = jax.block_until_ready(res)
                rec = driver.account(res)
            records.append(rec)
            # kept answers wait on the host, so that how many calls the
            # window held does not change the device's peak memory
            kept.extend(dataclasses.replace(
                a, f=np.asarray(a.f), g=np.asarray(a.g),
                cost=np.asarray(a.cost)) for a in driver.keep(i, res, rec))
            del res
            i += 1
            if time.perf_counter() - t_first >= args.seconds:
                break
        t_last = time.perf_counter()
        window_compiles = meter.since(mark)["compiles"]
        summary = None
        if trace_dir:
            jax.profiler.stop_trace()
            summary = tracefile.summarize(*tracefile.read_xplane(
                tracefile.find_xplane(trace_dir)))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    peak = peak_bytes(devices)
    runrec = Run(device_kind=devices[0].device_kind,
                 chips=cell.chips, setup_s=t_first - t0,
                 window_s=t_last - t_first, calls=records,
                 window_compiles=window_compiles, peak_bytes=peak,
                 trace=summary)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = load_module(bench_root, "metrics", m["name"]).read(runrec)
        if value is not None:
            metrics[m["name"]] = dict(value=value, unit=m["unit"])

    driver.release()
    rng = np.random.default_rng([args.seed & 0xFFFFFFFF, args.seed >> 32])
    checks = check_answers(cell, driver.sample(kept, rng), reference)
    parts = checks.pop("_parts")
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    device = dict(_device_info(devices), memory_peak_bytes=peak)
    result = dict(correct=correct, attempted=len(records),
                  failed=sum(r.failed for r in records), metrics=metrics,
                  device=device)
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = dict(
            device_ops=[[k, v] for k, v in summary.device_ops],
            idle_gaps=[[k, v] for k, v in summary.idle_gaps])
    result["checks"] = checks
    print(json.dumps(dict(window_s=runrec.window_s, calls=len(records),
                          iters=[r.iters for r in records],
                          window_compiles=window_compiles, parts=parts)),
          file=out, flush=True)
    for name, c in checks.items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=err,
              flush=True)
    print(json.dumps(result), file=out, flush=True)
    return 0


def main(argv=None, t0=None) -> int:
    return run(argv, t0=t0)
