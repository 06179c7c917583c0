"""Closed loop of ``solve(spec)`` over a few large point-cloud problems.

Set-up makes ``instances`` problems of the configuration's sizes on the
device in one jitted call: a pool drawn from ``pool_seed``, re-expressed by
the run's seed (``datagen.reexpress``), each with its Lemma-1 anchors, and
one ``SolveSpec`` per problem. Call ``i`` solves problem ``i % instances``
of an order the seed draws; featurization is inside the call.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import datagen
from bench.harness import Answer, CallRecord, ClosedLoopDriver, Problem, failed

GENERATORS = {"higgs_standin": datagen.higgs_standin}


def make_problems(config: dict, count: int, pool_seed: int, seed: int,
                  shard=None):
    """``count`` problems on the device: the pool's, re-expressed by ``seed``,
    in an order ``seed`` draws. ``shard`` places the points (rows) and the
    anchors (replicated) of each."""
    n, d, r, eps = config["n"], config["d"], config["r"], config["eps"]
    gen = GENERATORS[config["generator"]]

    def one(pool_key, seed_key):
        kd, ka = jax.random.split(pool_key)
        x, y = gen(kd, n, d)
        radius = jnp.maximum(jnp.max(jnp.linalg.norm(x, axis=1)),
                             jnp.max(jnp.linalg.norm(y, axis=1)))
        z = jax.random.normal(ka, (r, d), jnp.float32)
        return (*datagen.reexpress(seed_key, x, y, z), radius)

    out_shardings = None
    if shard is not None:
        rows, repl = shard
        out_shardings = [(rows, rows, repl, repl)] * count
    order = np.random.default_rng(
        [seed & 0xFFFFFFFF, seed >> 32]).permutation(count)
    keys = [(datagen.key_of(pool_seed, int(i)), datagen.key_of(seed, int(i)))
            for i in order]
    made = jax.jit(lambda ks: [one(*k) for k in ks],
                   out_shardings=out_shardings)(keys)
    radii = jax.device_get([m[3] for m in made])
    problems = []
    for (x, y, z, _), R in zip(made, radii):
        scale = jnp.sqrt(datagen.gaussian_q(float(R), eps, d) * eps / 4.0)
        problems.append(Problem(x=x, y=y, anchors=scale * z, eps=float(eps),
                                R=float(R)))
    return problems


class Driver(ClosedLoopDriver):
    def __init__(self, config, params, seed, devices):
        super().__init__(config, params, seed, devices)
        self.problems = make_problems(config, int(params["instances"]),
                                      int(params["pool_seed"]), seed,
                                      self.placement())
        self.specs = [self.spec(p) for p in self.problems]

    def placement(self):
        return None

    def policy(self):
        from repro.core.objective import ExecutionPolicy
        return ExecutionPolicy()

    def spec(self, p: Problem):
        from repro.core.geometry import GaussianPointCloud
        from repro.core.spec import SolveSpec
        c = self.config
        geom = GaussianPointCloud.build(p.x, p.y, p.anchors, eps=p.eps, R=p.R)
        return SolveSpec(geometry=geom, method=c["method"], tol=c["tol"],
                         max_iter=c["max_iter"], policy=self.policy())

    def call(self, i):
        from repro.core import solve
        return solve(self.specs[i % len(self.specs)])

    def account(self, out) -> CallRecord:
        cost, n_iter, err = jax.device_get(
            (out.cost, out.n_iter, out.marginal_err))
        c = self.config
        return CallRecord(
            failed=failed(float(cost), float(err), c["tol"]),
            iters=int(n_iter),
            problems=[(c["n"], c["n"], c["r"], c["d"], int(n_iter))])

    def keep(self, i, out, record):
        p = self.problems[i % len(self.problems)]
        return [Answer(p, out.f, out.g, out.cost, record.iters)]

    def control_call(self, i, reference, precision):
        p = self.problems[i % len(self.problems)]
        return reference.solve(p.x, p.y, p.anchors, eps=p.eps, R=p.R,
                               tol=self.config["tol"],
                               max_iter=self.config["max_iter"],
                               precision=precision)

    def release(self):
        self.specs = []
