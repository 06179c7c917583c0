"""Quickstart: linear-time Sinkhorn divergences through the unified API.

    PYTHONPATH=src python examples/quickstart.py

One entry point — ``repro.core.solve`` — reaches every solver in the repo:

    problem = OTProblem.from_point_clouds(x, y, anchors, eps=0.5)
    res = solve(problem, method="log_factored")

Method selection cheat-sheet:
  "factored"       scaling-space O(r(n+m)) per iter — fastest at eps >~ 0.3
  "log_factored"   same cost, log-domain — the default; safe at any eps
  "accelerated"    Nesterov-AGM variant (Remark 2) — best iteration rate,
                   but its two-marginal error check doubles the f32 noise
                   floor: keep tol >= 1e-6 or it will report converged=False
  "quadratic"      dense O(nm) Cuturi baseline — ground truth at small n
  "log_quadratic"  dense log-domain — the oracle the tests compare against
  "sharded"        shard_map multi-device (pass mesh=...)
Schedule selection: pass ``EpsSchedule(eps_init=..., decay=...)`` whenever
the target eps is small (<= 0.05) and the problem was built from point
clouds or a cost matrix — the geometric eps cascade warm-starts each stage
and converges in fewer total iterations than a cold start.

Walks the paper's pipeline end to end:
  1. sample two clouds and build a geometry problem (Lemma-1 features);
  2. solve with the factored O(r(n+m)) path and the exact dense oracle;
  3. solve a small-eps problem with and without annealing;
  4. batch-solve a GAN-shaped minibatch with the vmapped engine;
  5. differentiate the divergence w.r.t. the cloud (envelope theorem).
"""
import time

import jax
import jax.numpy as jnp

from repro.core import (
    BatchedSinkhorn,
    EpsSchedule,
    OTProblem,
    data_radius,
    sinkhorn_divergence_gaussian,
    solve,
    solve_annealed,
)
from repro.core.features import GaussianFeatureMap
from repro.data import gaussian_clouds


def main():
    n, d, eps, r = 4000, 2, 0.5, 500
    x, y = gaussian_clouds(seed=0, n=n, d=d)
    R = float(data_radius(x, y))
    print(f"clouds: n={n}, d={d}, radius={R:.2f}, eps={eps}, r={r}")

    fm = GaussianFeatureMap(r=r, d=d, eps=eps, R=R)
    U = fm.init(jax.random.PRNGKey(0))
    problem = OTProblem.from_point_clouds(x, y, U, eps=eps, R=R)

    # --- exact (quadratic) reference through the same front-end ---
    t0 = time.perf_counter()
    ref = solve(problem, method="log_quadratic", tol=1e-6, max_iter=5000)
    t_ref = time.perf_counter() - t0
    print(f"exact ROT   = {float(ref.cost):+.5f}   ({t_ref:.2f}s, "
          f"{int(ref.n_iter)} iters, O(n^2) per iter)")

    # --- linear-time positive features (the paper; method='auto' picks it) ---
    t0 = time.perf_counter()
    rf = solve(problem, tol=1e-6, max_iter=5000)
    t_rf = time.perf_counter() - t0
    dev = abs(float(rf.cost - ref.cost) / ref.cost) * 100
    print(f"RF ROT      = {float(rf.cost):+.5f}   ({t_rf:.2f}s, "
          f"{int(rf.n_iter)} iters, O(nr) per iter) — {dev:.2f}% off")

    # --- small eps: annealing cuts iterations ---
    small = OTProblem.from_point_clouds(x[:500], y[:500], U, eps=0.02, R=R)
    cold = solve(small, method="log_factored", tol=1e-4, max_iter=50000)
    ann = solve_annealed(small, method="log_factored", tol=1e-4,
                         max_iter=50000,
                         schedule=EpsSchedule(eps_init=0.8, decay=0.4))
    print(f"eps=0.02    : cold {int(cold.n_iter)} iters vs annealed "
          f"{int(ann.result.n_iter)} iters over {len(ann.stage_eps)} stages "
          f"(same cost to {abs(float(ann.result.cost - cold.cost)):.1e})")

    # --- GAN-shaped minibatch: one vmapped engine call, B problems ---
    B, nb = 8, 256
    xs = x[: B * nb].reshape(B, nb, d)
    ys = y[: B * nb].reshape(B, nb, d)
    engine = BatchedSinkhorn(eps=eps, method="log_factored", tol=1e-6,
                             max_iter=2000)
    t0 = time.perf_counter()
    batch = engine.solve_point_clouds(xs, ys, U, R=R)
    t_b = time.perf_counter() - t0
    print(f"batched     : {B} problems of n={nb} in {t_b:.2f}s, costs "
          f"[{float(batch.cost.min()):+.4f}, {float(batch.cost.max()):+.4f}]")

    # --- differentiable Sinkhorn divergence (envelope theorem) ---
    div_fn = jax.jit(lambda x_: sinkhorn_divergence_gaussian(
        x_, y, U, eps=eps, q=fm.q, tol=1e-6, max_iter=2000))
    grad_fn = jax.jit(jax.grad(lambda x_: sinkhorn_divergence_gaussian(
        x_, y, U, eps=eps, q=fm.q, tol=1e-6, max_iter=2000)))
    div = float(div_fn(x))
    g = grad_fn(x)
    print(f"divergence  = {div:+.5f}; |grad wrt locations| = "
          f"{float(jnp.linalg.norm(g)):.4f} "
          f"(envelope theorem — no backprop through the loop)")

    # gradient step moves the cloud closer
    x2 = x - 50.0 * g
    print(f"after one gradient step: divergence = {float(div_fn(x2)):+.5f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
