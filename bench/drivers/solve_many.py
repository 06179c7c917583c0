"""Closed loop of ``solve_many(specs)`` over batches of small point clouds.

Set-up makes a pool of ``batches`` batches of ``batch`` pairs on the device
in one jitted call, drawn from ``pool_seed`` and re-expressed pair by pair
by the run's seed (``datagen.reexpress``), each pair with its own Lemma-1
anchors, and one list of ``SolveSpec`` per batch. The seed also draws the
order of the batches and of the lanes in each. Call ``i`` solves batch
``i % batches`` in one ``solve_many``; featurization is inside the call.
The check reads ``sample`` lanes drawn from the seed among ``keep`` lanes
(one unless set) kept per call, together with the lane that ran the most
iterations.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import datagen
from bench.harness import Answer, CallRecord, ClosedLoopDriver, Problem, failed

GENERATORS = {"pointcloud_standin": datagen.pointcloud_standin}


class Driver(ClosedLoopDriver):
    def __init__(self, config, params, seed, devices):
        super().__init__(config, params, seed, devices)
        c = config
        self.batch, self.batches = int(params["batch"]), int(params["batches"])
        self.lanes_sampled = int(params["sample"])
        self.keep_lanes = int(params.get("keep", 1))
        n, d, r, eps, R = c["n"], c["d"], c["r"], float(c["eps"]), float(c["R"])
        gen = GENERATORS[c["generator"]]
        scale = float(np.sqrt(datagen.gaussian_q(R, eps, d) * eps / 4.0))
        total = self.batch * self.batches

        self.rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32])
        # pair j of the pool goes to lane slot[j] of the run
        slot = np.concatenate([
            b * self.batch + self.rng.permutation(self.batch)
            for b in self.rng.permutation(self.batches)])

        def make(pool_key, seed_key):
            def one(pk, sk):
                kd, ka = jax.random.split(pk)
                x, y = gen(kd, n)
                u = scale * jax.random.normal(ka, (r, d), jnp.float32)
                return datagen.reexpress(sk, x, y, u)

            x, y, u = jax.vmap(one)(jax.random.split(pool_key, total),
                                    jax.random.split(seed_key, total))
            return [(x[j], y[j], u[j]) for j in range(total)]

        lanes = jax.jit(make)(datagen.key_of(int(params["pool_seed"])),
                              datagen.key_of(seed))
        self.problems = [None] * total
        for j, (x, y, u) in enumerate(lanes):
            self.problems[slot[j]] = Problem(x=x, y=y, anchors=u, eps=eps,
                                             R=R)
        self.specs = [[self.spec(p) for p in self._batch(b)]
                      for b in range(self.batches)]
        self.longest = None

    def _batch(self, b):
        return self.problems[b * self.batch:(b + 1) * self.batch]

    def spec(self, p: Problem):
        from repro.core.geometry import GaussianPointCloud
        from repro.core.objective import ExecutionPolicy
        from repro.core.spec import SolveSpec
        c = self.config
        geom = GaussianPointCloud.build(p.x, p.y, p.anchors, eps=p.eps, R=p.R)
        return SolveSpec(geometry=geom, method=c["method"], tol=c["tol"],
                         max_iter=c["max_iter"], policy=ExecutionPolicy())

    def call(self, i):
        from repro.core import solve_many
        return solve_many(self.specs[i % self.batches])

    def account(self, out) -> CallRecord:
        got = jax.device_get([(r.cost, r.n_iter, r.marginal_err) for r in out])
        c = self.config
        tol = c["tol"]
        self._lanes = [int(it) for _, it, _ in got]
        return CallRecord(
            failed=any(failed(float(cost), float(err), tol)
                       for cost, _, err in got),
            iters=max(self._lanes),
            problems=[(c["n"], c["n"], c["r"], c["d"], it)
                      for it in self._lanes])

    def keep(self, i, out, record):
        problems = self._batch(i % self.batches)
        lanes = self.rng.choice(len(out), size=min(self.keep_lanes, len(out)),
                                replace=False)
        top = int(np.argmax(self._lanes))
        if self.longest is None or self._lanes[top] > self.longest.n_iter:
            r = out[top]
            self.longest = Answer(problems[top], r.f, r.g, r.cost,
                                  self._lanes[top])
        return [Answer(problems[j], out[j].f, out[j].g, out[j].cost,
                       self._lanes[j]) for j in lanes]

    def sample(self, kept, rng):
        pick = rng.choice(len(kept), size=min(self.lanes_sampled, len(kept)),
                          replace=False)
        chosen = [kept[j] for j in sorted(pick)]
        if self.longest is not None:
            chosen.append(self.longest)
        return chosen

    def control_call(self, i, reference, precision):
        problems = self._batch(i % self.batches)
        xs = jnp.stack([p.x for p in problems])
        ys = jnp.stack([p.y for p in problems])
        us = jnp.stack([p.anchors for p in problems])
        p0 = problems[0]
        solve = functools.partial(
            reference.solve, eps=p0.eps, R=p0.R, tol=self.config["tol"],
            max_iter=self.config["max_iter"], precision=precision)
        res = jax.vmap(solve)(xs, ys, us)
        return [type(res)(*(leaf[j] for leaf in res))
                for j in range(len(problems))]

    def release(self):
        self.specs = []
