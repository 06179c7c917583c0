"""Smoke run of the solver, the service and the sharded solve on a TPU.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --four-chips # four chips: the sharded solve only

Everything runs in this one process, through the public front doors
(``SolveSpec`` -> ``solve`` / ``solve_many``, and ``OTService``) with the
default ``ExecutionPolicy``; no kernel is called directly. Data is made
from ``--seed``.

* Preflight: the device must be a TPU, the kernel backend ``tpu-mosaic``,
  and no selected plan may run Pallas in interpret mode.
* A: a log-domain Gaussian point-cloud solve, n = m = 2^19, d = 32,
  r = 256, eps = 0.1, against the same spec on the XLA operators.
* B: ``solve_many`` over 16 problems, n = m = 1024, r = 256, eps = 0.5, in
  the scaling and the log domain; the megakernel block step must be
  selected, and every lane must match the XLA operators.
* C: an ``OTService`` without recovery: warm-up, then 32 ragged requests
  (n, m in 200-2000, r = 64) with no compile, no runner fault, and costs
  that match ``solve`` on the XLA operators.
* --four-chips: a ``sharded_log`` solve on a mesh of 4 devices, n = m =
  2^20, r = 256, against the same problem on device 0 alone.

Each phase prints one JSON line. Any failed check raises, so the script
exits non-zero; only a run whose every check passed prints the last line,
``{"ok": true, "device": {...}}``. JAX's persistent compilation cache is
on (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import GaussianFeatureMap, solve, solve_many  # noqa: E402
from repro.core.geometry import (  # noqa: E402
    FactoredPositive, GaussianPointCloud, data_radius)
from repro.core.objective import ExecutionPolicy  # noqa: E402
from repro.core.spec import SolveSpec  # noqa: E402
from repro.data import gaussian_clouds, highdim_clouds  # noqa: E402
from repro.kernels.backend import resolve_backend  # noqa: E402
from repro.kernels.ops import observe_plan_selection  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.serving import OTService  # noqa: E402

XLA = ExecutionPolicy(use_pallas=False)


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


class CompileMeter:
    """Backend compiles (each one a compile request, a cache hit or a
    fresh compile), their seconds, and persistent-cache hits."""

    def __init__(self):
        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self):
        return self.compiles, self.seconds, self.cache_hits

    def since(self, mark) -> dict:
        return dict(compiles=self.compiles - mark[0],
                    compile_s=round(self.seconds - mark[1], 3),
                    cache_hits=self.cache_hits - mark[2])


def peak_bytes(device=None):
    stats = (device or jax.devices()[0]).memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def plans(events) -> list:
    return sorted({f"{e['mode']}/{e['kind']}/{e['step']}"
                   f"/interpret={e['interpret']}" for e in events})


def cloud_geometry(x, y, r, eps, key):
    """A Lemma-1 Gaussian point cloud with r anchors drawn for its radius."""
    R = float(data_radius(x, y))
    anchors = GaussianFeatureMap(r=r, d=x.shape[1], eps=eps, R=R).init(key)
    return GaussianPointCloud.build(x, y, anchors, eps=eps, R=R)


def timed_solve(spec):
    t0 = time.perf_counter()
    res = jax.block_until_ready(solve(spec))
    return res, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_a(meter, *, seed=0, n=2 ** 19, d=32, r=256, eps=0.1, tol=1e-6,
            max_iter=500, policy=ExecutionPolicy()):
    """A large log-domain point-cloud solve vs the XLA operators."""
    x, y = highdim_clouds(seed, n, d)
    geom = cloud_geometry(x, y, r, eps, jax.random.PRNGKey(seed + 1))
    spec = SolveSpec(geometry=geom, tol=tol, max_iter=max_iter,
                     policy=policy)
    mark = meter.mark()
    with observe_plan_selection() as events:
        res, wall = timed_solve(spec)
    fused_compiles = meter.since(mark)
    ref, ref_wall = timed_solve(spec.replace(policy=XLA))
    cost, ref_cost = float(res.cost), float(ref.cost)
    err, ref_err = float(res.marginal_err), float(ref.marginal_err)
    out = dict(
        phase="A", n=n, m=n, d=d, r=r, eps=eps, tol=tol, max_iter=max_iter,
        plans=plans(events), cost=cost, cost_xla=ref_cost,
        rel_cost_diff=rel(cost, ref_cost), marginal_err=err,
        marginal_err_xla=ref_err, iters=int(res.n_iter),
        iters_xla=int(ref.n_iter), wall_s=round(wall, 3),
        wall_s_xla=round(ref_wall, 3), **fused_compiles,
        peak_bytes_in_use=peak_bytes())
    print(json.dumps(out), flush=True)
    check(np.isfinite(cost) and np.isfinite(ref_cost), "A: cost not finite")
    check(np.isfinite(err) and np.isfinite(ref_err),
          "A: marginal error not finite")
    check(out["rel_cost_diff"] <= 1e-4, "A: cost differs from XLA")
    check(err <= 2.0 * ref_err, "A: marginal error worse than 2x XLA's")
    check(any(e["mode"] == "log" for e in events),
          "A: no fused log plan selected")
    return events


def phase_b(meter, *, seed=100, count=16, n=1024, r=256, eps=0.5, tol=1e-6,
            max_iter=2000, policy=ExecutionPolicy()):
    """solve_many in the megakernel regime, scaling and log domain."""
    geoms = []
    for i in range(count):
        x, y = gaussian_clouds(seed + i, n, 2)
        geoms.append(cloud_geometry(x, y, r, eps,
                                    jax.random.PRNGKey(seed + 1000 + i)))
    # the reference checks at the megakernel's cadence, so both stop at
    # the same block boundary
    xla = ExecutionPolicy(use_pallas=False, check_every=8)
    events_all = []
    for method in ("factored", "log_factored"):
        specs = [SolveSpec(geometry=g, method=method, tol=tol,
                           max_iter=max_iter, policy=policy) for g in geoms]
        mark = meter.mark()
        with observe_plan_selection() as events:
            t0 = time.perf_counter()
            res = jax.block_until_ready(solve_many(specs))
            wall = time.perf_counter() - t0
        compiles = meter.since(mark)
        ref = solve_many([s.replace(policy=xla) for s in specs])
        costs = np.array([float(v.cost) for v in res])
        ref_costs = np.array([float(v.cost) for v in ref])
        diffs = np.abs(costs - ref_costs) / np.abs(ref_costs)
        out = dict(
            phase="B", method=method, problems=count, n=n, m=n, r=r,
            eps=eps, plans=plans(events),
            max_rel_cost_diff=float(diffs.max()),
            iters=[int(v.n_iter) for v in res],
            iters_xla=[int(v.n_iter) for v in ref],
            max_marginal_err=max(float(v.marginal_err) for v in res),
            max_marginal_err_xla=max(float(v.marginal_err) for v in ref),
            wall_s=round(wall, 3), **compiles,
            peak_bytes_in_use=peak_bytes())
        print(json.dumps(out), flush=True)
        check(bool(np.all(np.isfinite(costs))), f"B/{method}: cost not finite")
        check(events and all(e["step"] == "megakernel" for e in events),
              f"B/{method}: megakernel block step not selected: {events}")
        check(out["max_rel_cost_diff"] <= 1e-5,
              f"B/{method}: a lane's cost differs from XLA")
        events_all += events
    return events_all


def _service_requests(seed, r, eps):
    """32 ragged requests in three bucket cells, eight per megabatch, as
    log-factored specs (features made here, before the serving window)."""
    rng = np.random.default_rng(seed)
    sizes = ([(rng.integers(200, 257), rng.integers(400, 513))
              for _ in range(8)]
             + [(rng.integers(900, 1025), rng.integers(900, 1025))
                for _ in range(8)]
             + [(rng.integers(1600, 2001), rng.integers(1100, 2001))
                for _ in range(16)])
    cells = [(256, 512, r), (1024, 1024, r), (2048, 2048, r)]
    # one compile per request shape, not one per eager op
    log_features = jax.jit(lambda g: g.log_features())
    specs = []
    for i, (n, m) in enumerate(sizes):
        x = rng.normal(size=(int(n), 2)).astype(np.float32)
        y = (0.7 * rng.normal(size=(int(m), 2)) + 0.5).astype(np.float32)
        geom = cloud_geometry(jnp.asarray(x), jnp.asarray(y), r, eps,
                              jax.random.PRNGKey(seed + i))
        lxi, lzt = log_features(geom)
        a = rng.dirichlet(np.full(int(n), 4.0)).astype(np.float32)
        b = rng.dirichlet(np.full(int(m), 4.0)).astype(np.float32)
        specs.append(SolveSpec(
            geometry=FactoredPositive(log_xi=lxi, log_zeta=lzt, eps=eps),
            a=jnp.asarray(a), b=jnp.asarray(b), method="log_factored"))
    return specs, cells


def phase_c(meter, *, seed=200, r=64, eps=0.5, max_batch=8,
            policy=ExecutionPolicy()):
    """The service: warm-up, then 32 ragged requests, no compile."""
    specs, cells = _service_requests(seed, r, eps)
    svc = OTService(eps=eps, method="log_factored", max_batch=max_batch,
                    max_wait=0.0, recovery=None,
                    use_pallas=policy.use_pallas,
                    inner_steps=policy.inner_steps,
                    check_every=policy.check_every)
    mark = meter.mark()
    with observe_plan_selection() as events:
        t0 = time.perf_counter()
        built = svc.warmup(cells, batches=[max_batch])
        warm_s = time.perf_counter() - t0
    warm_compiles = meter.since(mark)
    misses = svc.runners.misses
    mark = meter.mark()
    t0 = time.perf_counter()
    tickets = [svc.submit(s) for s in specs]
    svc.drain()
    serve_s = time.perf_counter() - t0
    window = meter.since(mark)
    stats = svc.stats()
    xla = ExecutionPolicy(use_pallas=False, check_every=8)
    diffs = [rel(float(t.result.cost),
                 float(solve(s.replace(policy=xla)).cost))
             for t, s in zip(tickets, specs)]
    out = dict(
        phase="C", requests=len(specs), cells=[list(c) for c in cells],
        r=r, eps=eps, runners_built=built, plans=plans(events),
        warmup_s=round(warm_s, 3), warmup_compiles=warm_compiles["compiles"],
        serve_s=round(serve_s, 3), compiles_after_warmup=window["compiles"],
        runner_misses_after_warmup=stats["runner"]["misses"] - misses,
        extra_traces=stats["runner"]["extra_traces"],
        batches=stats["batches"],
        runner_faults=stats["recovery"]["runner_faults"],
        max_rel_cost_diff=max(diffs), peak_bytes_in_use=peak_bytes())
    print(json.dumps(out), flush=True)
    check(all(t.done and t.result is not None for t in tickets),
          "C: a request was not answered")
    check(out["compiles_after_warmup"] == 0
          and out["runner_misses_after_warmup"] == 0
          and out["extra_traces"] == 0, "C: compiled after warm-up")
    check(out["runner_faults"] == 0, "C: runner faults")
    check(out["max_rel_cost_diff"] <= 1e-4, "C: a cost differs from solve()")
    check(bool(events), "C: no fused plan selected")
    return events


def phase_four_chips(meter, *, seed=300, n=2 ** 20, d=32, r=256, eps=0.1,
                     tol=1e-6, max_iter=500):
    """sharded_log on a mesh of 4 devices vs device 0 alone."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()
    check(len(devices) == 4, f"--four-chips needs 4 devices, "
                             f"found {len(devices)}")
    mesh = Mesh(np.array(devices), ("data",))
    x, y = highdim_clouds(seed, n, d)
    geom = cloud_geometry(x, y, r, eps, jax.random.PRNGKey(seed + 1))
    rows = NamedSharding(mesh, P("data", None))
    sharded = geom.__class__(x=jax.device_put(geom.x, rows),
                             y=jax.device_put(geom.y, rows),
                             anchors=geom.anchors, eps=geom.eps, R=geom.R)
    spec = SolveSpec(geometry=sharded, method="sharded_log", tol=tol,
                     max_iter=max_iter, policy=ExecutionPolicy(mesh=mesh))
    mark = meter.mark()
    res, wall = timed_solve(spec)
    compiles = meter.since(mark)
    one = jax.device_put((geom.x, geom.y, geom.anchors), devices[0])
    local = SolveSpec(geometry=geom.__class__(
        x=one[0], y=one[1], anchors=one[2], eps=geom.eps, R=geom.R),
        tol=tol, max_iter=max_iter)
    ref, ref_wall = timed_solve(local)

    def spread(arr):
        return sorted((s.device.id, s.data.shape[0])
                      for s in arr.addressable_shards)

    inputs, outputs = spread(sharded.x), spread(res.f)
    cost, ref_cost = float(res.cost), float(ref.cost)
    out = dict(
        phase="four_chips", n=n, m=n, d=d, r=r, eps=eps,
        input_shards=inputs, potential_shards=outputs, cost=cost,
        cost_one_device=ref_cost, rel_cost_diff=rel(cost, ref_cost),
        marginal_err=float(res.marginal_err),
        marginal_err_one_device=float(ref.marginal_err),
        iters=int(res.n_iter), iters_one_device=int(ref.n_iter),
        wall_s=round(wall, 3), wall_s_one_device=round(ref_wall, 3),
        **compiles,
        peak_bytes_in_use=[peak_bytes(dev) for dev in devices])
    print(json.dumps(out), flush=True)
    for what, spread_ in (("inputs", inputs), ("potentials", outputs)):
        check(len({dev for dev, _ in spread_}) == 4
              and all(rows_ == n // 4 for _, rows_ in spread_),
              f"four_chips: {what} not split over 4 devices: {spread_}")
    check(np.isfinite(cost) and np.isfinite(ref_cost),
          "four_chips: cost not finite")
    check(out["rel_cost_diff"] <= 1e-4,
          "four_chips: sharded cost differs from one device")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def preflight() -> dict:
    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU: JAX's first device is {dev.platform!r}")
    be = resolve_backend()
    check(be.name == "tpu-mosaic" and not be.interpret,
          f"kernel backend is {be.name!r}, not 'tpu-mosaic'")
    return dict(platform=dev.platform, kind=dev.device_kind,
                count=len(jax.devices()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded solve on a mesh of 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    device = preflight()
    cache_dir = enable_compile_cache()
    meter = CompileMeter()
    print(json.dumps(dict(phase="preflight", device=device,
                          backend=resolve_backend().name,
                          compile_cache=cache_dir)), flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(meter, seed=300 + args.seed)
    else:
        events = (phase_a(meter, seed=args.seed)
                  + phase_b(meter, seed=100 + args.seed)
                  + phase_c(meter, seed=200 + args.seed))
        check(all(e["interpret"] is False for e in events),
              "a selected plan runs Pallas in interpret mode")
    print(json.dumps(dict(phase="total", wall_s=round(
        time.perf_counter() - t0, 3), **meter.since((0, 0.0, 0)))),
        flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
