"""Section 3.1 claim: O(r(n+m)) vs O(nm) per-iteration scaling in n.

Fixed iteration count (tol=0, max_iter fixed) isolates per-iteration cost;
the log-log slope of time vs n should be ~1 for RF and ~2 for Sin.

``--mesh`` adds the distributed axis: per-iteration time of the sharded
solver (scaling AND log mode) vs device count, over meshes of the first
1, 2, 4, ... ``--devices`` devices, plus the derived per-iteration
collective overhead vs the 1-device run — the measured twin of the
EXPERIMENTS.md §Roofline psum-cost estimate. Before JAX starts its
backend the script asks the CPU platform for ``--devices`` virtual
devices (``--xla_force_host_platform_device_count``, which only the CPU
backend reads); on an accelerator with fewer devices than asked for it
exits with an error instead of measuring anything else.
"""
from __future__ import annotations

import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    gaussian_features,
    sinkhorn_factored,
    sinkhorn_quadratic,
    squared_euclidean,
)
from repro.core.features import GaussianFeatureMap
from repro.data import gaussian_clouds


def _time(fn, *args, reps=3):
    fn(*args)[0].block_until_ready()        # compile + warm
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args)[0].block_until_ready()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def main(n_list=(500, 1000, 2000, 4000), r: int = 256, eps: float = 0.5,
         iters: int = 50):
    rows = []
    for n in n_list:
        x, y = gaussian_clouds(0, n, 2)
        a = jnp.full((n,), 1.0 / n)
        b = jnp.full((n,), 1.0 / n)
        R = 4.0
        fm = GaussianFeatureMap(r=r, d=2, eps=eps, R=R)
        U = fm.init(jax.random.PRNGKey(0))
        xi = gaussian_features(x, U, eps=eps, q=fm.q)
        zt = gaussian_features(y, U, eps=eps, q=fm.q)
        K = jnp.exp(-squared_euclidean(x, y) / eps)

        rf = jax.jit(lambda xi_, zt_: (sinkhorn_factored(
            xi_, zt_, a, b, eps=eps, tol=0.0, max_iter=iters).u,))
        sin = jax.jit(lambda K_: (sinkhorn_quadratic(
            K_, a, b, eps=eps, tol=0.0, max_iter=iters).u,))
        t_rf = _time(rf, xi, zt)
        t_sin = _time(sin, K)
        rows.append((n, t_rf, t_sin))

    ns = np.array([r[0] for r in rows], float)
    slope = lambda ts: np.polyfit(np.log(ns), np.log(np.array(ts)), 1)[0]
    s_rf = slope([r[1] for r in rows])
    s_sin = slope([r[2] for r in rows])
    print("name,us_per_call,derived")
    for n, t_rf, t_sin in rows:
        print(f"scaling/RF/n{n},{t_rf * 1e6:.1f},iters={iters};r={r}")
        print(f"scaling/Sin/n{n},{t_sin * 1e6:.1f},iters={iters}")
    print(f"scaling/RF/slope,0,loglog_slope={s_rf:.2f}")
    print(f"scaling/Sin/slope,0,loglog_slope={s_sin:.2f}")
    return s_rf, s_sin


def main_mesh(n: int = 4096, r: int = 256, eps: float = 0.5,
              iters: int = 30, device_counts=(1, 2, 4, 8)):
    """Sharded iteration time vs device count (CPU virtual devices).

    Fixed iteration count isolates per-iteration cost; each mesh uses the
    first p of the forced host devices. The derived ``collective_us`` row
    is t(p) - t(1)/p-ideal — on CPU "devices" this measures the psum /
    psum-LSE dispatch overhead, the term that stays O(r) on real ICI.
    """
    from jax.sharding import Mesh

    from repro.core import FactoredPositive, sharded_sinkhorn_geometry

    devices = jax.devices()
    counts = [p for p in device_counts if p <= len(devices)]
    key = jax.random.PRNGKey(0)
    xi = jax.random.uniform(key, (n, r)) + 0.05
    zt = jax.random.uniform(jax.random.fold_in(key, 1), (n, r)) + 0.05
    a = jnp.full((n,), 1.0 / n)

    rows = []
    base = {}
    for mode in ("scaling", "log"):
        for p in counts:
            mesh = Mesh(np.array(devices[:p]), ("data",))
            fn = jax.jit(lambda xi_, zt_, _m=mesh, _mode=mode: \
                sharded_sinkhorn_geometry(
                    _m, FactoredPositive(xi=xi_, zeta=zt_, eps=eps),
                    a, a, mode=_mode, tol=0.0, max_iter=iters).f)
            fn(xi, zt).block_until_ready()      # compile + warm
            ts = []
            for _ in range(3):
                t0 = time.perf_counter()
                fn(xi, zt).block_until_ready()
                ts.append(time.perf_counter() - t0)
            us_it = min(ts) / iters * 1e6
            if p == 1:
                base[mode] = us_it
            comm = us_it - base[mode] / p
            rows.append(
                f"scaling/mesh/{mode}/p{p},{us_it:.1f},"
                f"n={n};r={r};iters={iters};collective_us={comm:.1f}")
    print("name,us_per_call,derived")
    for row in rows:
        print(row)
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", action="store_true",
                    help="measure sharded iteration time vs device count")
    ap.add_argument("--devices", type=int, default=8,
                    help="largest mesh (default 8); on CPU this many "
                         "virtual devices are created")
    args = ap.parse_args()
    if args.mesh:
        # read when the backend starts, i.e. at the first jax.devices()
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
        devices = jax.devices()
        if len(devices) < args.devices:
            sys.exit(f"bench_scaling --mesh: asked for {args.devices} "
                     f"devices, the {devices[0].platform} backend has "
                     f"{len(devices)}; pass --devices {len(devices)}")
        counts = tuple(2 ** k for k in range(args.devices.bit_length())
                       if 2 ** k <= args.devices)
        main_mesh(device_counts=counts)
    else:
        main()
