"""Faults planted under the timed path, at the front door's output.

Each is a ``call(driver, i)`` for ``harness.run``: it runs the program and
breaks what the call returns the way a faulty solver would.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _each(out, fn):
    return [fn(r) for r in out] if isinstance(out, list) else fn(out)


def unchanged_state(drv, i):
    """The step returns its state unchanged: the initial potentials, f = g = 0."""
    return _each(drv.call(i), lambda r: r._replace(
        f=jnp.zeros_like(r.f), g=jnp.zeros_like(r.g),
        cost=jnp.zeros_like(r.cost)))


def answer_altered(drv, i):
    """Each returned cost altered by 0.1% where it is produced."""
    return _each(drv.call(i), lambda r: r._replace(cost=r.cost * 1.001))


def half_batch(drv, i):
    """Half of the batch left out, the mean taken over the rest: lanes of the
    second half get the first half's answers; a single solve runs on the
    first half of each cloud's points and repeats their potentials."""
    from repro.core import solve, solve_many
    if hasattr(drv, "batches"):
        specs = drv.specs[i % drv.batches]
        half = solve_many(specs[:len(specs) // 2])
        return half + half
    spec = drv.specs[i % len(drv.specs)]
    geom = spec.geometry
    n = geom.x.shape[0] // 2
    res = solve(spec.replace(geometry=type(geom)(
        x=geom.x[:n], y=geom.y[:n], anchors=geom.anchors, eps=geom.eps,
        R=geom.R)))
    return res._replace(f=jnp.concatenate([res.f, res.f]),
                        g=jnp.concatenate([res.g, res.g]))


def no_exchange(drv, i):
    """The exchange between chips left out: each chip solves its own rows
    alone, and the potentials are put back together."""
    from repro.core import solve
    from repro.core.geometry import GaussianPointCloud
    from repro.core.spec import SolveSpec
    p = drv.problems[i % len(drv.problems)]
    parts = len(drv.devices)
    n = p.x.shape[0] // parts
    dev = drv.devices[0]
    x, y, u = jax.device_put((p.x, p.y, p.anchors), dev)
    results = [solve(SolveSpec(
        geometry=GaussianPointCloud.build(x[k * n:(k + 1) * n],
                                          y[k * n:(k + 1) * n], u,
                                          eps=p.eps, R=p.R),
        method="log_factored", tol=drv.config["tol"],
        max_iter=drv.config["max_iter"])) for k in range(parts)]
    r0 = results[0]
    return r0._replace(f=jnp.concatenate([r.f for r in results]),
                       g=jnp.concatenate([r.g for r in results]),
                       cost=sum(r.cost for r in results) / parts)


FAULTS = {"unchanged_state": unchanged_state, "answer_altered": answer_altered,
          "half_batch": half_batch, "no_exchange": no_exchange}
