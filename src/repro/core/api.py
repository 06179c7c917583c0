"""Unified solver front-end: ``solve`` / ``BatchedSinkhorn`` / ``EpsSchedule``.

Every solver variant in the repo (scaling-space factored, log-domain
factored, accelerated AGM, dense quadratic baselines, signed Nystrom,
arc-cosine, separable-grid, shard_map distributed) is reachable through ONE
entry point:

    problem = OTProblem.from_point_clouds(x, y, anchors, eps=0.05)
    res = solve(problem, method="log_factored",
                schedule=EpsSchedule(eps_init=1.0, decay=0.5))

and batches of independent problems — the GAN-minibatch workload of the
paper's Section 4, and the "heavy traffic" serving shape of the ROADMAP —
go through the vmapped engine:

    engine = BatchedSinkhorn(eps=0.05, method="log_factored")
    results = engine.solve_many(problems)      # buckets, pads, vmaps

Design notes
------------
* **The Geometry protocol carries the kernel.** An :class:`OTProblem` is a
  thin ``(geometry, a, b)`` record; the geometry (``repro.core.geometry``)
  owns the kernel representation — features, log-features, dense cost,
  point clouds + anchors, Nystrom factors, or grid axes — and exposes the
  operators every solver consumes. There is no representation branching
  here: a ``method`` picks an *algorithm* (scaling-space, log-domain,
  accelerated, densified baseline, sharded) from a dispatch table, and
  every kernel application inside it routes through the geometry.
* **One kernel, many algorithms.** For a problem built from (log-)features
  the quadratic methods run on the *induced* cost ``C = -eps log(Xi Zeta^T)``
  (``geometry.cost_matrix()``) so all methods share one fixed point and
  agree to solver tolerance (the oracle-consistency contract tested in
  ``tests/test_api.py``). Problems built from point clouds use the true
  squared-Euclidean cost for the quadratic methods — the paper's ``Sin``
  baseline — so there the factored methods differ by the
  feature-approximation error (Theorem 3.1).
* **Annealing** (``EpsSchedule``) runs a geometric cascade
  ``eps_0 > eps_0*decay > ... > eps`` re-deriving each stage's kernel via
  ``geometry.rebuild_at(eps_k)`` and warm-starting the potentials (f, g) —
  equivalently ``u = e^{f/eps}`` — between stages. At small eps this cuts
  total iterations by a large factor versus a cold start (property-tested
  in ``tests/test_schedule.py``). Families whose kernel is pinned to one
  eps (explicit features, arc-cosine, Nystrom) cannot be annealed.
* **Batching** pads each problem's supports up to the power-of-two buckets
  in ``configs/shapes.py`` (``ot_bucket``) with ZERO-weight atoms — exact,
  not approximate, because every solver masks zero weights (see
  ``sinkhorn.masked_dual_value``) — groups problems by padded shape, and
  ``vmap``s the shared solver loop over the group. One ``lax.while_loop``
  then drives the whole batch: per-iteration work is a single batched thin
  contraction instead of B separate GEMV dispatches, which is where the
  >= 3x wall-clock win of ``benchmarks/bench_batch.py`` comes from.
"""
from __future__ import annotations

import dataclasses
import math
import warnings
from collections import OrderedDict
from functools import partial
from typing import Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..configs.shapes import OTBatchShape
from .accelerated import accelerated_sinkhorn_geometry
from .geometry import (
    ArcCosinePointCloud,
    DenseCost,
    FactoredPositive,
    GaussianPointCloud,
    Geometry,
    GridSeparable,
    NystromLowRank,
    data_radius,
)
from .sinkhorn import (
    SinkhornResult,
    sinkhorn_geometry,
    sinkhorn_log_geometry,
)

__all__ = [
    "METHODS",
    "OTProblem",
    "EpsSchedule",
    "AnnealedResult",
    "BatchedSinkhorn",
    "solve",
    "solve_annealed",
    "solve_many",
    "unpad_result",
    "get_engine",
    "engine_cache_info",
    "set_engine_cache_capacity",
    "clear_engine_cache",
]

METHODS = (
    "auto",
    "factored",
    "log_factored",
    "accelerated",
    "quadratic",
    "log_quadratic",
    "arccos",
    "nystrom",
    "sharded",
    "sharded_log",
)


# ---------------------------------------------------------------------------
# Problem specification: a thin (geometry, a, b) record
# ---------------------------------------------------------------------------


def _uniform(n: int, dtype) -> jax.Array:
    return jnp.full((n,), 1.0 / n, dtype)


@dataclasses.dataclass(frozen=True)
class OTProblem:
    """One entropic OT problem: a Geometry (the kernel) plus marginals.

    The geometry owns the kernel representation; ``a``/``b`` are the
    measure weights (zeros allowed — zero-weight atoms are masked exactly
    by every solver, which is what makes bucket padding exact). The
    ``from_*`` constructors below are the stable public surface; kernel
    views (features, costs) live on the geometry itself.
    """

    geometry: Geometry
    a: jax.Array                       # (n,) weights, sum 1 (zeros allowed)
    b: jax.Array                       # (m,)

    def __post_init__(self):
        if not isinstance(self.geometry, Geometry):
            raise TypeError(
                "OTProblem.geometry must be a Geometry; build one via the "
                "from_* constructors or repro.core.geometry"
            )

    @property
    def eps(self) -> float:
        return self.geometry.eps

    # -- constructors -------------------------------------------------------

    @classmethod
    def from_geometry(cls, geometry: Geometry, a=None, b=None) -> "OTProblem":
        n, m = geometry.shape
        a = _uniform(n, jnp.float32) if a is None else a
        b = _uniform(m, jnp.float32) if b is None else b
        return cls(geometry=geometry, a=a, b=b)

    @classmethod
    def from_features(cls, xi, zeta, a=None, b=None, *, eps: float) -> "OTProblem":
        return cls.from_geometry(
            FactoredPositive(xi=xi, zeta=zeta, eps=eps),
            _uniform(xi.shape[0], xi.dtype) if a is None else a,
            _uniform(zeta.shape[0], zeta.dtype) if b is None else b,
        )

    @classmethod
    def from_log_features(cls, log_xi, log_zeta, a=None, b=None, *,
                          eps: float) -> "OTProblem":
        return cls.from_geometry(
            FactoredPositive(log_xi=log_xi, log_zeta=log_zeta, eps=eps),
            _uniform(log_xi.shape[0], log_xi.dtype) if a is None else a,
            _uniform(log_zeta.shape[0], log_zeta.dtype) if b is None else b,
        )

    @classmethod
    def from_cost(cls, C, a=None, b=None, *, eps: float) -> "OTProblem":
        return cls.from_geometry(
            DenseCost(C, eps),
            _uniform(C.shape[0], C.dtype) if a is None else a,
            _uniform(C.shape[1], C.dtype) if b is None else b,
        )

    @classmethod
    def from_point_clouds(cls, x, y, anchors, a=None, b=None, *, eps: float,
                          R: Optional[float] = None) -> "OTProblem":
        return cls.from_geometry(
            GaussianPointCloud.build(x, y, anchors, eps=eps, R=R),
            _uniform(x.shape[0], x.dtype) if a is None else a,
            _uniform(y.shape[0], y.dtype) if b is None else b,
        )

    @classmethod
    def from_grid(cls, axes_x, axes_y=None, a=None, b=None, *,
                  eps: float) -> "OTProblem":
        """Separable-grid problem (images / histograms): measures live on
        the cartesian product of the axis coordinates, weights in C order
        (``image.reshape(-1)``)."""
        return cls.from_geometry(
            GridSeparable.build(axes_x, axes_y, eps=eps), a, b
        )

    @property
    def anneal_capable(self) -> bool:
        return self.geometry.anneal_capable


# ---------------------------------------------------------------------------
# Epsilon annealing
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EpsSchedule:
    """Geometric eps cascade: eps_0, eps_0*decay, ... down to the target.

    Intermediate stages only need to hand a decent warm start to the next
    stage, so they stop at a LOOSE tolerance: stage tolerances decay
    geometrically from ``stage_tol`` down to ``sqrt(stage_tol * tol)`` —
    the final stage does the last push to ``tol`` (``stage_tols``). At run
    time each stage's target is additionally capped at the previous stage's
    ACHIEVED error, which makes the per-stage marginal error non-increasing
    by construction. Each intermediate stage is also capped at
    ``stage_iters`` iterations; the final stage gets the caller's full
    ``max_iter``.
    """

    eps_init: float
    decay: float = 0.5
    stage_iters: int = 400
    stage_tol: float = 1e-2

    def __post_init__(self):
        if not (0.0 < self.decay < 1.0):
            raise ValueError(f"decay must be in (0, 1), got {self.decay}")
        if self.eps_init <= 0:
            raise ValueError("eps_init must be positive")

    def stages(self, eps_final: float) -> Tuple[float, ...]:
        if self.eps_init <= eps_final:
            return (eps_final,)
        out = []
        e = self.eps_init
        # stop the geometric ladder once e is within sqrt(decay) of the
        # target and jump straight there — a penultimate stage a few
        # percent above eps_final would cost a full solve for no progress
        thresh = eps_final / math.sqrt(self.decay)
        while e > thresh:
            out.append(e)
            e *= self.decay
        out.append(eps_final)
        return tuple(out)

    def stage_tols(self, tol_final: float, n_stages: int) -> Tuple[float, ...]:
        """Per-stage marginal-error targets: geometric from ``stage_tol``
        down to sqrt(stage_tol * tol_final) across the intermediates, then
        ``tol_final``. Keeping intermediates loose is what buys the total-
        iteration win — tight intermediate solves at large eps do not
        transfer into a proportionally better warm start."""
        if n_stages <= 1 or self.stage_tol <= tol_final:
            return (tol_final,) * max(n_stages, 1)
        if n_stages == 2:
            return (self.stage_tol, tol_final)
        mid = math.sqrt(self.stage_tol * tol_final)
        ratio = (mid / self.stage_tol) ** (1.0 / (n_stages - 2))
        tols = [max(self.stage_tol * ratio**k, tol_final)
                for k in range(n_stages - 1)]
        return tuple(tols) + (tol_final,)


class AnnealedResult(NamedTuple):
    result: SinkhornResult            # final-stage solve (n_iter = TOTAL)
    stage_eps: Tuple[float, ...]
    stage_iters: jax.Array            # (S,) iterations per stage
    stage_errs: jax.Array             # (S,) marginal error at stage exit


# ---------------------------------------------------------------------------
# Dispatch: method -> (geometry coercion, solver runner)
# ---------------------------------------------------------------------------
#
# A method names an ALGORITHM; the geometry supplies the kernel operators.
# Coercers turn the problem's geometry into the one the algorithm runs on
# (identity for native methods, densification for the quadratic baselines,
# cost-family conversion for arccos / nystrom); runners call the matching
# operator-generic solver. No kernel application happens outside a Geometry.


def _run_scaling(geom, a, b, *, tol, max_iter, momentum, f_init, g_init,
                 mesh, mesh_axis, use_pallas=None, inner_steps=None,
                 check_every=None, precision="highest"):
    u_init = None if f_init is None else jnp.exp(f_init / geom.eps)
    return sinkhorn_geometry(
        geom, a, b, tol=tol, max_iter=max_iter, momentum=momentum,
        u_init=u_init, use_pallas=use_pallas, inner_steps=inner_steps,
        check_every=check_every, precision=precision,
    )


def _run_log(geom, a, b, *, tol, max_iter, momentum, f_init, g_init,
             mesh, mesh_axis, use_pallas=None, inner_steps=None,
             check_every=None, precision="highest"):
    return sinkhorn_log_geometry(
        geom, a, b, tol=tol, max_iter=max_iter, momentum=momentum,
        f_init=f_init, g_init=g_init, use_pallas=use_pallas,
        inner_steps=inner_steps, check_every=check_every,
        precision=precision,
    )


def _run_accelerated(geom, a, b, *, tol, max_iter, momentum, f_init, g_init,
                     mesh, mesh_axis, use_pallas=None, inner_steps=None,
                     check_every=None, precision="highest"):
    # AGM's Nesterov extrapolation IS its acceleration — an extra
    # over-relaxation has no defined place in the scheme, so reject rather
    # than silently drop it. The dual-gradient structure also keeps this
    # solver on the XLA log-operators (use_pallas is ignored), so the
    # megakernel block (inner_steps) is rejected too; the check cadence
    # applies as everywhere else.
    if momentum != 1.0:
        raise ValueError(
            "momentum (over-relaxation) is not supported by "
            "method='accelerated': the AGM extrapolation already plays "
            f"that role; got momentum={momentum}. Use momentum=1.0 or a "
            "plain method ('factored', 'log_factored', ...)."
        )
    if inner_steps is not None and int(inner_steps) > 1:
        raise ValueError(
            "inner_steps > 1 (the persistent megakernel) is not available "
            "for method='accelerated': the AGM body interleaves gradient "
            "extrapolation with exact block steps and has no fused plan. "
            "Use check_every= for the cadence win, or a plain method."
        )
    if precision != "highest":
        raise ValueError(
            "method='accelerated' differentiates the smoothed dual through "
            "its log-operators; the bf16 storage policy is not supported "
            f"here (got precision={precision!r})"
        )
    return accelerated_sinkhorn_geometry(
        geom, a, b, tol=tol, max_iter=max_iter, f_init=f_init, g_init=g_init,
        check_every=1 if check_every is None else check_every,
    )


def _run_sharded(geom, a, b, *, tol, max_iter, momentum, f_init, g_init,
                 mesh, mesh_axis, use_pallas=None, inner_steps=None,
                 check_every=None, precision="highest", mode="scaling"):
    from .sharded import sharded_sinkhorn_geometry

    if mesh is None:
        raise ValueError(f"method='sharded{'_log' * (mode == 'log')}' "
                         "requires a mesh=...")
    return sharded_sinkhorn_geometry(
        mesh, geom, a, b, axis=mesh_axis, mode=mode, tol=tol,
        max_iter=max_iter, momentum=momentum, f_init=f_init, g_init=g_init,
        inner_steps=inner_steps, check_every=check_every,
        precision=precision, use_pallas=use_pallas,
    )


def _coerce_native_factored(geom, eps, *, rank, key):
    if isinstance(geom, DenseCost):
        raise ValueError(
            "no factored kernel available (dense-cost problem); use a "
            "quadratic method or build the problem from point clouds"
        )
    return geom


def _coerce_identity(geom, eps, *, rank, key):
    return geom


def _coerce_densify(geom, eps, *, rank, key):
    if isinstance(geom, DenseCost):
        return geom
    return DenseCost(geom.cost_matrix(), eps)


def _coerce_arccos(geom, eps, *, rank, key):
    if isinstance(geom, ArcCosinePointCloud):
        return geom
    if isinstance(geom, GaussianPointCloud):
        # swap the cost family on the same supports: fresh arc-cosine
        # anchors (u ~ N(0, sigma^2 I)), rank defaulting to the problem's
        # existing anchor count
        from .features import ArcCosineFeatureMap

        r = geom.anchors.shape[0] if rank is None else rank
        fm = ArcCosineFeatureMap(r=r, d=geom.x.shape[-1])
        anchors = fm.init(jax.random.PRNGKey(0) if key is None else key)
        return ArcCosinePointCloud(
            geom.x, geom.y, anchors, eps=eps, s=fm.s, sigma=fm.sigma,
            kappa=fm.kappa,
        )
    raise ValueError(
        "method='arccos' needs point-cloud supports (an ArcCosinePointCloud "
        f"or GaussianPointCloud geometry); got {type(geom).__name__}"
    )


def _coerce_nystrom(geom, eps, *, rank, key):
    if isinstance(geom, NystromLowRank):
        return geom
    if isinstance(geom, (GaussianPointCloud, ArcCosinePointCloud)):
        r = geom.anchors.shape[0] if rank is None else rank
        return NystromLowRank.from_point_clouds(
            geom.x, geom.y, eps=eps, rank=r,
            key=jax.random.PRNGKey(0) if key is None else key,
        )
    raise ValueError(
        "method='nystrom' needs point-cloud supports (a NystromLowRank or "
        f"point-cloud geometry); got {type(geom).__name__}"
    )


# method -> (coerce geometry, runner). The only dispatch table in the file.
_SOLVERS: Dict[str, Tuple[Callable, Callable]] = {
    "factored": (_coerce_native_factored, _run_scaling),
    "log_factored": (_coerce_native_factored, _run_log),
    "accelerated": (_coerce_native_factored, _run_accelerated),
    "quadratic": (_coerce_densify, _run_scaling),
    "log_quadratic": (_coerce_densify, _run_log),
    "arccos": (_coerce_arccos, _run_log),
    "nystrom": (_coerce_nystrom, _run_scaling),
    "sharded": (_coerce_native_factored,
                partial(_run_sharded, mode="scaling")),
    "sharded_log": (_coerce_native_factored,
                    partial(_run_sharded, mode="log")),
}

# auto-dispatch table: first matching geometry type wins; factored
# geometries carrying linear-space features prefer the scaling solver.
_AUTO_METHODS: Tuple[Tuple[type, str], ...] = (
    (NystromLowRank, "nystrom"),
    (ArcCosinePointCloud, "arccos"),
    (DenseCost, "log_quadratic"),
    (GridSeparable, "log_factored"),
    (GaussianPointCloud, "log_factored"),
)


def _auto_method(problem: OTProblem, mesh=None) -> str:
    g = problem.geometry
    local = None
    for typ, meth in _AUTO_METHODS:
        if isinstance(g, typ):
            local = meth
            break
    if local is None:
        local = ("factored"
                 if isinstance(g, FactoredPositive) and g.xi is not None
                 else "log_factored")
    if mesh is None:
        return local
    # mesh given: select the sharded execution mode, scaling vs log
    # EXACTLY like the local table — explicit linear factors keep the
    # scaling iteration, every other family runs the psum'd-LSE log
    # domain (mandatory at the small eps where scalings over/underflow)
    return "sharded" if local == "factored" else "sharded_log"


def _solve_stage(
    problem: OTProblem,
    method: str,
    eps: float,
    *,
    tol: float,
    max_iter: int,
    momentum: float,
    f_init: Optional[jax.Array],
    g_init: Optional[jax.Array],
    mesh=None,
    mesh_axis: str = "data",
    rank: Optional[int] = None,
    key: Optional[jax.Array] = None,
    use_pallas: Optional[bool] = None,
    inner_steps: Optional[int] = None,
    check_every: Optional[int] = None,
    precision: str = "highest",
    donate: bool = False,
) -> SinkhornResult:
    """One solve at a fixed eps with optional warm-started potentials.

    ``donate=True`` routes the stage through a jitted runner that DONATES
    the warm-start potentials (``f_init``/``g_init``): an annealed cascade
    re-solving at each eps then reuses the previous stage's potential
    buffers instead of holding two copies live per stage. Only taken when
    the potentials are concrete arrays (donating under an outer trace is
    meaningless) and the solve is single-device.
    """
    if method not in _SOLVERS:
        raise ValueError(f"unknown method {method!r}; expected one of {METHODS}")
    if mesh is not None and not method.startswith("sharded"):
        # a mesh must never be silently dropped: local methods with a
        # sharded twin are promoted (matching solve_many's mapping),
        # everything else is rejected rather than run single-device
        twin = _SHARDED_TWIN.get(method)
        if twin is None or twin == "auto":
            raise ValueError(
                f"method={method!r} does not run on a mesh; with mesh= use "
                "method='auto', 'factored'/'sharded', or "
                "'log_factored'/'sharded_log'"
            )
        method = twin
    coerce, run = _SOLVERS[method]
    geom = coerce(problem.geometry.rebuild_at(eps), eps, rank=rank, key=key)
    if (donate and mesh is None
            and isinstance(f_init, jax.Array)
            and isinstance(g_init, jax.Array)
            and not isinstance(f_init, jax.core.Tracer)
            and not isinstance(g_init, jax.core.Tracer)):
        fn = _donating_stage_runner(
            method, int(max_iter), float(momentum), use_pallas,
            inner_steps, check_every, precision,
        )
        return fn(geom, problem.a, problem.b, f_init, g_init, tol)
    return run(
        geom, problem.a, problem.b, tol=tol, max_iter=max_iter,
        momentum=momentum, f_init=f_init, g_init=g_init, mesh=mesh,
        mesh_axis=mesh_axis, use_pallas=use_pallas,
        inner_steps=inner_steps, check_every=check_every,
        precision=precision,
    )


_DONATING_STAGE_CACHE: Dict[Tuple, Callable] = {}


def _donating_stage_runner(method, max_iter, momentum, use_pallas,
                           inner_steps, check_every, precision) -> Callable:
    """Jitted per-stage runner with the warm-start potentials donated.

    Keyed on every trace-time constant; the geometry rides as a pytree
    argument (its static metadata — eps, kinds — keys the jit cache), so
    an annealing cascade compiles one executable per stage eps and the
    potentials handed from stage k to stage k+1 give their buffers back.
    """
    key = (method, max_iter, momentum, use_pallas, inner_steps,
           check_every, precision)
    fn = _DONATING_STAGE_CACHE.get(key)
    if fn is None:
        run = _SOLVERS[method][1]

        @partial(jax.jit, donate_argnums=(3, 4))
        def fn(geom, a, b, f_init, g_init, tol):
            return run(
                geom, a, b, tol=tol, max_iter=max_iter, momentum=momentum,
                f_init=f_init, g_init=g_init, mesh=None, mesh_axis="data",
                use_pallas=use_pallas, inner_steps=inner_steps,
                check_every=check_every, precision=precision,
            )

        _DONATING_STAGE_CACHE[key] = fn
    return fn


def solve_annealed(
    problem: OTProblem,
    *,
    method: str = "auto",
    schedule: EpsSchedule,
    tol: float = 1e-6,
    max_iter: int = 2000,
    momentum: float = 1.0,
    mesh=None,
    mesh_axis: str = "data",
    rank: Optional[int] = None,
    key: Optional[jax.Array] = None,
    use_pallas: Optional[bool] = None,
    inner_steps: Optional[int] = None,
    check_every: Optional[int] = None,
    precision: str = "highest",
) -> AnnealedResult:
    """Annealed solve with per-stage diagnostics.

    Each stage solves at eps_k re-deriving the kernel via
    ``geometry.rebuild_at``, then hands its potentials (f, g) to the next
    stage as warm start. The returned ``result.n_iter`` is the TOTAL across
    stages so it compares directly against a cold-start solve's iteration
    count.
    """
    if method == "auto":
        method = _auto_method(problem, mesh)
    if not problem.geometry.anneal_capable:
        raise ValueError(
            "eps-annealing needs a geometry whose kernel is re-derivable at "
            f"any eps; {type(problem.geometry).__name__} pins the kernel to "
            "one eps. Build the problem from point clouds, a dense cost, or "
            "grid axes to enable annealing."
        )
    # NOTE: the stage loop below (ladder tols, prev_err cap, warm-started
    # f/g, total-iteration accumulation) has a vmap-compatible twin in
    # BatchedSinkhorn._make_cloud_solver — keep their semantics in sync.
    stages = schedule.stages(problem.eps)
    tols = schedule.stage_tols(tol, len(stages))
    f = g = None
    prev_err = None
    stage_iters, stage_errs = [], []
    res = None
    for k, e in enumerate(stages):
        last = k == len(stages) - 1
        # cap at the previous stage's achieved error -> per-stage marginal
        # error is non-increasing by construction
        tol_k = tols[k] if prev_err is None else jnp.minimum(tols[k], prev_err)
        res = _solve_stage(
            problem, method, e,
            tol=tol_k,
            max_iter=max_iter if last else schedule.stage_iters,
            momentum=momentum, f_init=f, g_init=g,
            mesh=mesh, mesh_axis=mesh_axis, rank=rank, key=key,
            use_pallas=use_pallas, inner_steps=inner_steps,
            check_every=check_every, precision=precision,
            # warm-started stages donate the previous stage's potential
            # buffers (two fewer live (n,)+(m,) copies per stage)
            donate=k > 0,
        )
        prev_err = res.marginal_err
        f, g = res.f, res.g
        stage_iters.append(res.n_iter)
        stage_errs.append(res.marginal_err)
    total = jnp.sum(jnp.stack(stage_iters))
    final = res._replace(n_iter=total)
    return AnnealedResult(
        final, stages, jnp.stack(stage_iters), jnp.stack(stage_errs)
    )


def solve(
    problem: OTProblem,
    *,
    method: str = "auto",
    schedule: Optional[EpsSchedule] = None,
    tol: float = 1e-6,
    max_iter: int = 2000,
    momentum: float = 1.0,
    mesh=None,
    mesh_axis: str = "data",
    rank: Optional[int] = None,
    key: Optional[jax.Array] = None,
    use_pallas: Optional[bool] = None,
    inner_steps: Optional[int] = None,
    check_every: Optional[int] = None,
    precision: str = "highest",
) -> SinkhornResult:
    """Solve one entropic OT problem with any solver variant in the repo.

    The preferred calling convention is ONE argument — a
    :class:`~repro.core.spec.SolveSpec` — which carries the geometry,
    weights, target (tol/max_iter/schedule) and an
    :class:`~repro.core.objective.ExecutionPolicy`::

        solve(SolveSpec(geometry=geom, tol=1e-6,
                        policy=ExecutionPolicy(precision="bf16")))

    The keyword form below remains as a back-compat wrapper; passing the
    legacy execution kwargs (``use_pallas=``/``inner_steps=``/
    ``check_every=``/``precision=``) with a bare problem emits a
    ``DeprecationWarning``.

    ``method``: "auto" | "factored" | "log_factored" | "accelerated" |
    "quadratic" | "log_quadratic" | "arccos" | "nystrom" | "sharded" |
    "sharded_log" (both need ``mesh``). "auto" dispatches on the
    problem's geometry type (and onto the sharded twins under ``mesh``).
    ``schedule``: optional :class:`EpsSchedule` eps-annealing cascade
    (anneal-capable geometries only).
    ``rank``/``key``: optional knobs for the cost-family converting
    methods — "arccos" draws ``rank`` fresh arc-cosine anchors with
    ``key``; "nystrom" samples ``rank`` landmarks with ``key``. A
    Nystrom run that blows up at small eps reports
    ``result.diverged == True`` (the paper's Fig. 1/3/5 failure mode)
    instead of handing back unexplained NaNs.
    ``mesh``/``mesh_axis``: run on a device mesh — with ``method="auto"``
    the solver picks the sharded execution mode matching the local table
    (scaling for explicit linear factors, psum'd-LSE log domain for
    everything else); ``method="sharded"``/``"sharded_log"`` force one.
    Supports shard over ``mesh_axis`` (padded with inert zero-weight
    atoms when ``n % p != 0``); per-iteration cross-device traffic is a
    single r-vector collective.
    ``use_pallas``: route the solver hot loop through the fused Pallas
    plan the geometry declares (``None`` = auto-on when the backend
    compiles Pallas, i.e. TPU; ``True`` forces it — interpret mode
    off-TPU; ``False`` forces the XLA operators). Families without a
    fused plan fall back to XLA operators either way.
    ``inner_steps``: iterations fused into ONE persistent megakernel
    launch (``kernels.fused_loop``: factors VMEM-resident, potentials
    on-chip, marginal error only at block boundaries) when the fused
    plan offers one. ``check_every``: convergence-check cadence in
    iterations (must be a multiple of ``inner_steps``); the XLA paths
    get the same fewer-syncs win from it. Auto (both ``None``): 8/8 on
    compiled TPU fused plans whose factors fit VMEM, 1/1 everywhere
    else. Converged results always satisfy ``err <= tol``; ``n_iter``
    becomes a multiple of the cadence and ``max_iter`` rounds up to one.
    Sharded methods reject ``inner_steps > 1`` (the block would drop the
    per-iteration psum) but honor ``check_every``.
    ``precision``: ``"highest"`` (default) or ``"bf16"`` — the
    mixed-precision execution policy: kernel factors (features,
    log-features, dense Gibbs kernels, low-rank factors) are STORED and
    STREAMED in bfloat16, halving the HBM bytes the memory-bound
    iteration streams, while every contraction and LSE accumulates in
    f32. Expect cost agreement with fp32 at the bf16 relative rounding
    (~1e-2 on potentials at moderate eps; tighter on costs); keep
    ``"highest"`` for small-eps log solves where log-features span
    hundreds of nats.
    """
    call = obs.count("ot.solve.calls")
    with obs.span("ot.solve", call=call):
        from .spec import SolveSpec  # lazy: spec imports this module

        if isinstance(problem, SolveSpec):
            spec = problem
            if spec.recovery is not None:
                from ..resilience.ladder import solve_with_recovery
                return solve_with_recovery(spec).result
            kw = spec.solver_kwargs()
            kw.pop("method")
            kw.pop("schedule")
            with spec.policy.scope():
                prob = spec.problem()
                meth = spec.method
                if meth == "auto":
                    meth = _auto_method(prob, spec.policy.mesh)
                if spec.schedule is not None:
                    return solve_annealed(
                        prob, method=meth, schedule=spec.schedule, **kw
                    ).result
                return _solve_stage(
                    prob, meth, prob.eps, f_init=None, g_init=None, **kw)
        if (use_pallas is not None or inner_steps is not None
                or check_every is not None or precision != "highest"):
            warnings.warn(
                "passing execution kwargs (use_pallas=/inner_steps=/"
                "check_every=/precision=) to solve() directly is deprecated: "
                "build a SolveSpec with an ExecutionPolicy "
                "(repro.core.spec) and call solve(spec)",
                DeprecationWarning, stacklevel=2)
        if method == "auto":
            method = _auto_method(problem, mesh)
        if schedule is not None:
            return solve_annealed(
                problem, method=method, schedule=schedule, tol=tol,
                max_iter=max_iter, momentum=momentum, mesh=mesh,
                mesh_axis=mesh_axis, rank=rank, key=key, use_pallas=use_pallas,
                inner_steps=inner_steps, check_every=check_every,
                precision=precision,
            ).result
        return _solve_stage(
            problem, method, problem.eps, tol=tol, max_iter=max_iter,
            momentum=momentum, f_init=None, g_init=None, mesh=mesh,
            mesh_axis=mesh_axis, rank=rank, key=key, use_pallas=use_pallas,
            inner_steps=inner_steps, check_every=check_every,
            precision=precision,
        )


# ---------------------------------------------------------------------------
# Batched engine
# ---------------------------------------------------------------------------


def _pad_rows(arr: jax.Array, n_pad: int, *, replicate: bool,
              fill: float = 0.0) -> jax.Array:
    """Pad axis 0 to n_pad: replicate the last row (features / supports —
    keeps log-features finite) or append ``fill`` (0 for weights/scalings,
    -inf for the sharded path's padded log-potentials). Shared by the
    batched engine and ``core.sharded`` so the padding semantics live in
    one place."""
    pad = n_pad - arr.shape[0]
    if pad <= 0:
        return arr
    if replicate:
        tail = jnp.broadcast_to(arr[-1:], (pad,) + arr.shape[1:])
    else:
        tail = jnp.full((pad,) + arr.shape[1:], fill, arr.dtype)
    return jnp.concatenate([arr, tail], axis=0)


# Batched-engine dispatch: method -> (stacked kernel data -> Geometry).
# ka/kb are one problem's slices of the stacked arrays; the builders run
# INSIDE the vmapped solver body, so every kernel application in the
# batched hot loop routes through the same Geometry operators as the
# single-problem path.
_ENGINE_GEOMETRIES: Dict[str, Callable[..., Geometry]] = {
    "factored": lambda ka, kb, eps: FactoredPositive(xi=ka, zeta=kb, eps=eps),
    "log_factored": lambda ka, kb, eps: FactoredPositive(
        log_xi=ka, log_zeta=kb, eps=eps),
    "accelerated": lambda ka, kb, eps: FactoredPositive(
        log_xi=ka, log_zeta=kb, eps=eps),
    "quadratic": lambda ka, kb, eps: DenseCost(ka, eps),
    "log_quadratic": lambda ka, kb, eps: DenseCost(ka, eps),
}

# runners are shared with the single-problem path: same method, same
# algorithm, whether vmapped or not
_ENGINE_RUNNERS: Dict[str, Callable] = {
    m: _SOLVERS[m][1] for m in _ENGINE_GEOMETRIES
}


class BatchedSinkhorn:
    """vmapped solver engine for batches of independent OT problems.

    All problems in a batch share the feature rank r (same anchors in the
    GAN workload); supports are padded to the power-of-two buckets of
    ``configs.shapes.ot_bucket`` with zero-weight atoms, which the masked
    solvers treat exactly. One jitted ``vmap`` of the shared solver loop
    drives each bucket group, so per-iteration work is one batched thin
    contraction instead of B separate kernel dispatches.

    Stacked entry points (``solve_stacked``, ``solve_point_clouds``) take
    already-uniform (B, ...) arrays; ``solve_many`` handles ragged problem
    lists via bucketing. Each per-problem solve constructs its Geometry
    from the stacked slices inside the vmapped body, so the batched path
    shares the operator implementations with everything else.
    """

    _FACTORED = ("factored", "log_factored", "accelerated")
    _QUADRATIC = ("quadratic", "log_quadratic")

    def __init__(
        self,
        *,
        eps: float,
        method: str = "log_factored",
        tol: float = 1e-6,
        max_iter: int = 2000,
        momentum: float = 1.0,
        schedule: Optional[EpsSchedule] = None,
        use_pallas: Optional[bool] = None,
        inner_steps: Optional[int] = None,
        check_every: Optional[int] = None,
        precision: str = "highest",
    ):
        if method not in self._FACTORED + self._QUADRATIC:
            raise ValueError(
                f"batched engine supports {self._FACTORED + self._QUADRATIC}, "
                f"got {method!r}"
            )
        self.eps = eps
        self.method = method
        self.tol = tol
        self.max_iter = max_iter
        self.momentum = momentum
        self.schedule = schedule
        # threaded into the vmapped solver bodies: vmap over the fused
        # Pallas kernels adds B as a leading grid axis, so the whole bucket
        # group runs through one fused plan — or one megakernel block
        # (inner_steps) — per iteration; check_every/precision apply the
        # shared cadence and mixed-precision policies per problem
        self.use_pallas = use_pallas
        self.inner_steps = inner_steps
        self.check_every = check_every
        self.precision = precision
        if schedule is not None and method not in ("log_factored",
                                                   "accelerated"):
            raise ValueError(
                "batched annealing runs in log domain (small-eps stages); "
                f"use method='log_factored' or 'accelerated', got {method!r}"
            )
        self._build_geometry = _ENGINE_GEOMETRIES[method]
        self._runner = _ENGINE_RUNNERS[method]
        self._vsolve_features = jax.jit(jax.vmap(self._solve_one))
        # warm-started twin: the incoming potentials are DONATED, so a
        # re-solve loop (GAN steps, annealing drivers) reuses the previous
        # solve's (B, n)/(B, m) potential buffers instead of holding both
        self._vsolve_features_warm = jax.jit(
            jax.vmap(self._solve_one_warm), donate_argnums=(4, 5),
        )
        self._vsolve_clouds_cache: Dict[Tuple[int, float], Callable] = {}

    # -- single-problem bodies (vmapped) ------------------------------------

    def _solve_one(self, ka, kb, a, b) -> SinkhornResult:
        """ka/kb: (log-)features (n, r)/(m, r) — or (C, unused) dense."""
        geom = self._build_geometry(ka, kb, self.eps)
        return self._runner(
            geom, a, b, tol=self.tol, max_iter=self.max_iter,
            momentum=self.momentum, f_init=None, g_init=None,
            mesh=None, mesh_axis="data", use_pallas=self.use_pallas,
            inner_steps=self.inner_steps, check_every=self.check_every,
            precision=self.precision,
        )

    def _solve_one_warm(self, ka, kb, a, b, f0, g0) -> SinkhornResult:
        geom = self._build_geometry(ka, kb, self.eps)
        return self._runner(
            geom, a, b, tol=self.tol, max_iter=self.max_iter,
            momentum=self.momentum, f_init=f0, g_init=g0,
            mesh=None, mesh_axis="data", use_pallas=self.use_pallas,
            inner_steps=self.inner_steps, check_every=self.check_every,
            precision=self.precision,
        )

    def _make_cloud_solver(self, d: int, R: float):
        """Geometry-mode body: the GaussianPointCloud is rebuilt per
        annealing stage. ``anchors`` is a broadcast argument (shared
        across the batch).

        NOTE: the stage loop is the vmap-compatible twin of the one in
        :func:`solve_annealed` (log-domain only, no per-stage diagnostics)
        — keep their semantics in sync."""
        if self.schedule is not None:
            stages = self.schedule.stages(self.eps)
            tols = self.schedule.stage_tols(self.tol, len(stages))
        else:
            stages, tols = (self.eps,), (self.tol,)

        def solve_one(anchors, x, y, a, b) -> SinkhornResult:
            f = g = None
            prev_err = None
            total = jnp.array(0, jnp.int32)
            res = None
            for k, e in enumerate(stages):
                last = k == len(stages) - 1
                tol_k = (tols[k] if prev_err is None
                         else jnp.minimum(tols[k], prev_err))
                geom = GaussianPointCloud(x, y, anchors, eps=e, R=R)
                res = self._runner(
                    geom, a, b, tol=tol_k,
                    max_iter=(self.max_iter if last
                              else self.schedule.stage_iters),
                    momentum=self.momentum, f_init=f, g_init=g,
                    mesh=None, mesh_axis="data", use_pallas=self.use_pallas,
                    inner_steps=self.inner_steps,
                    check_every=self.check_every, precision=self.precision,
                )
                prev_err = res.marginal_err
                f, g = res.f, res.g
                total = total + res.n_iter
            return res._replace(n_iter=total)

        return solve_one

    # -- stacked entry points ------------------------------------------------

    def solve_stacked(self, ka, kb, a, b, f_init=None,
                      g_init=None) -> SinkhornResult:
        """Solve B problems given stacked kernel data.

        factored: ``ka``/``kb`` = features (B, n, r)/(B, m, r);
        log_factored/accelerated: log-features; quadratic/log_quadratic:
        ``ka`` = cost matrices (B, n, m) and ``kb`` is ignored (pass ``ka``).
        Returns a stacked :class:`SinkhornResult` (leading axis B).

        ``f_init``/``g_init`` (both (B, n)/(B, m)) warm-start the
        potentials and are DONATED to the jitted solver: pass the previous
        solve's ``res.f``/``res.g`` in a re-solve loop and their buffers
        are reused in place rather than held alongside the new ones.
        """
        if self.schedule is not None:
            raise ValueError(
                "stacked features pin the kernel to one eps — annealing "
                "needs solve_point_clouds (geometry mode)"
            )
        if (f_init is None) != (g_init is None):
            raise ValueError(
                "pass both f_init and g_init (or neither) — the warm-start "
                "entry donates the pair"
            )
        if f_init is None:
            return self._vsolve_features(ka, kb, a, b)
        return self._vsolve_features_warm(ka, kb, a, b, f_init, g_init)

    def solve_point_clouds(self, x, y, anchors, a=None, b=None, *,
                           R: Optional[float] = None) -> SinkhornResult:
        """Solve B cloud pairs (B, n, d)/(B, m, d) with SHARED anchors.

        The one batched mode that composes with an ``EpsSchedule`` —
        stage features are rebuilt inside the vmapped body.

        ``R`` is a trace-time constant (Lemma 1's q comes from scalar
        Lambert-W math), so each distinct R compiles a fresh solver. Pass a
        fixed bound when calling in a training loop; the default rounds the
        batch's data radius UP to the next 0.5 step (any upper bound is
        valid for Lemma 1) so minibatches of similar scale share a cache
        entry instead of recompiling every step.
        """
        if self.method not in ("log_factored", "accelerated"):
            raise ValueError("point-cloud mode runs in log domain")
        B, n, _ = x.shape
        m = y.shape[1]
        if a is None:
            a = jnp.full((B, n), 1.0 / n, x.dtype)
        if b is None:
            b = jnp.full((B, m), 1.0 / m, y.dtype)
        if R is None:
            radius = data_radius(x, y)
            if isinstance(radius, jax.core.Tracer):
                # float(tracer) below would raise an opaque
                # ConcretizationTypeError from inside jnp — fail with the
                # actionable message instead: R is a TRACE-TIME constant.
                raise ValueError(
                    "solve_point_clouds cannot derive the default R from "
                    "data values under jit/vmap tracing (R is a trace-time "
                    "constant — Lemma 1's q comes from scalar Lambert-W "
                    "math). Pass R= explicitly inside jit, e.g. a fixed "
                    "upper bound on max_i ||p_i||."
                )
            R = math.ceil(float(radius) * 2.0) / 2.0
        d = anchors.shape[-1]
        key = d, round(R, 6)
        fn = self._vsolve_clouds_cache.get(key)
        if fn is None:
            fn = jax.jit(jax.vmap(
                self._make_cloud_solver(d, R),
                in_axes=(None, 0, 0, 0, 0),
            ))
            self._vsolve_clouds_cache[key] = fn
        return fn(anchors, x, y, a, b)

    # -- ragged entry point --------------------------------------------------

    def solve_many(
        self,
        problems: Sequence[OTProblem],
        *,
        f_inits: Optional[Sequence[Optional[jax.Array]]] = None,
        g_inits: Optional[Sequence[Optional[jax.Array]]] = None,
    ) -> List[SinkhornResult]:
        """Solve a ragged list of problems: bucket by padded shape, pad with
        zero-weight atoms, vmap each bucket, unpad. Exact w.r.t. per-problem
        solves (masked zero weights), order-preserving.

        ``f_inits``/``g_inits`` optionally warm-start individual problems
        (per-problem ``(n_i,)``/``(m_i,)`` arrays, ``None`` entries cold-
        start). Any bucket containing at least one warm entry routes through
        the donated warm twin; cold entries inside such a bucket are padded
        with ZEROS, which is exactly the cold default (``f = 0`` is ``u = 1``
        in scaling space, and the log solver's ``_log_init`` starts from
        zeros before pinning dead atoms), so mixing warm and cold problems
        in one bucket stays elementwise-exact.
        """
        if (f_inits is None) != (g_inits is None):
            raise ValueError(
                "pass both f_inits and g_inits (or neither) — warm starts "
                "come as potential pairs"
            )
        if f_inits is not None:
            if len(f_inits) != len(problems) or len(g_inits) != len(problems):
                raise ValueError(
                    f"f_inits/g_inits must match problems "
                    f"({len(problems)}), got {len(f_inits)}/{len(g_inits)}"
                )
            for i, (fi, gi) in enumerate(zip(f_inits, g_inits)):
                if (fi is None) != (gi is None):
                    raise ValueError(
                        f"problem {i}: pass both f_init and g_init (or "
                        "neither)"
                    )
        groups: Dict[OTBatchShape, List[int]] = {}
        datas: Dict[int, Tuple[jax.Array, jax.Array]] = {}
        with obs.span("ot.stage"):
            for i, p in enumerate(problems):
                if float(p.eps) != float(self.eps):
                    raise ValueError(
                        f"problem {i} declares eps={p.eps} but this engine "
                        f"solves at eps={self.eps}; build one engine per eps"
                    )
                ka, kb = self.kernel_data(p)
                datas[i] = (ka, kb)
                groups.setdefault(self.batch_shape(ka, kb), []).append(i)

        out: List[Optional[SinkhornResult]] = [None] * len(problems)
        for shape, idxs in groups.items():
            with obs.span("ot.stage"):
                kas, kbs, aws, bws, f0s, g0s = [], [], [], [], [], []
                warm = f_inits is not None and any(
                    f_inits[i] is not None for i in idxs
                )
                for i in idxs:
                    p = problems[i]
                    ka, kb = self.pad_kernel_data(*datas[i], shape)
                    kas.append(ka)
                    kbs.append(kb)
                    aws.append(_pad_rows(p.a, shape.n_pad, replicate=False))
                    bws.append(_pad_rows(p.b, shape.m_pad, replicate=False))
                    if warm:
                        fi = f_inits[i]
                        gi = g_inits[i]
                        if fi is None:             # cold lane: zeros == cold
                            f0s.append(jnp.zeros((shape.n_pad,), p.a.dtype))
                            g0s.append(jnp.zeros((shape.m_pad,), p.b.dtype))
                        else:
                            f0s.append(_pad_rows(fi, shape.n_pad,
                                                 replicate=False))
                            g0s.append(_pad_rows(gi, shape.m_pad,
                                                 replicate=False))
                stacked = (jnp.stack(kas), jnp.stack(kbs),
                           jnp.stack(aws), jnp.stack(bws))
                if warm:
                    stacked += (jnp.stack(f0s), jnp.stack(g0s))
            if warm:
                res = self._vsolve_features_warm(*stacked)
            else:
                res = self._vsolve_features(*stacked)
            for j, i in enumerate(idxs):
                out[i] = unpad_result(res, j, problems[i].a.shape[0],
                                      problems[i].b.shape[0])
        return out

    # -- bucketing / padding helpers (shared with repro.serving) -------------

    def kernel_data(self, p: OTProblem) -> Tuple[jax.Array, jax.Array]:
        """The stacked-array representation of one problem's kernel under
        this engine's method: (log-)features for the factored methods, the
        dense cost (twice) for the quadratic ones."""
        geom = p.geometry.rebuild_at(self.eps)
        if self.method == "factored":
            return geom.features()
        if self.method in ("log_factored", "accelerated"):
            return geom.log_features()
        C = geom.cost_matrix()
        return C, C

    def batch_shape(self, ka: jax.Array, kb: jax.Array) -> OTBatchShape:
        """The bucket cell one problem's kernel data lands in — the key the
        ragged path groups by and the serving runner cache is keyed on."""
        if self.method in self._QUADRATIC:
            return OTBatchShape.for_quadratic(ka.shape[0], ka.shape[1])
        return OTBatchShape.for_problem(ka.shape[0], kb.shape[0], ka.shape[1])

    def pad_kernel_data(self, ka: jax.Array, kb: jax.Array,
                        shape: OTBatchShape) -> Tuple[jax.Array, jax.Array]:
        """Pad one problem's kernel data up to its bucket cell (replicated
        rows — exact, the added atoms carry zero weight)."""
        if self.method in self._QUADRATIC:
            ka = _pad_rows(ka, shape.n_pad, replicate=True)
            ka = _pad_rows(ka.T, shape.m_pad, replicate=True).T
            return ka, ka
        return (_pad_rows(ka, shape.n_pad, replicate=True),
                _pad_rows(kb, shape.m_pad, replicate=True))

    # deprecated private alias (pre-serving name)
    _kernel_data = kernel_data


def unpad_result(res: SinkhornResult, j: int, n: int, m: int) -> SinkhornResult:
    """Slice problem ``j`` out of a stacked bucket result, dropping the
    padded atoms: the inverse of the engine's bucket padding, shared by
    ``solve_many`` and the serving dispatch path."""
    return SinkhornResult(
        u=res.u[j, :n], v=res.v[j, :m],
        f=res.f[j, :n], g=res.g[j, :m],
        cost=res.cost[j], n_iter=res.n_iter[j],
        marginal_err=res.marginal_err[j],
        converged=res.converged[j],
    )


# ---------------------------------------------------------------------------
# Engine cache: LRU over solver configurations
# ---------------------------------------------------------------------------
#
# Every distinct (method, eps, tol, max_iter, ...) tuple owns a
# BatchedSinkhorn and thereby every jitted executable that engine ever
# compiled. Under service traffic with per-request tolerances that is a
# real leak, so the cache is a bounded LRU: least-recently-USED engines
# (and their executables) are dropped once the cap is hit. The stats feed
# the serving layer's cache accounting (``OTService.stats``).

_ENGINE_CACHE: "OrderedDict[Tuple, BatchedSinkhorn]" = OrderedDict()
_ENGINE_CACHE_CAPACITY = 8
_ENGINE_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0}


def engine_cache_info() -> Dict[str, int]:
    """Size/capacity/hit/miss/eviction counters of the ``solve_many``
    engine cache (copies — safe to diff across calls)."""
    return dict(size=len(_ENGINE_CACHE), capacity=_ENGINE_CACHE_CAPACITY,
                **_ENGINE_CACHE_STATS)


def set_engine_cache_capacity(capacity: int) -> None:
    """Re-cap the engine LRU; evicts oldest entries immediately if the new
    cap is below the current size."""
    global _ENGINE_CACHE_CAPACITY
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    _ENGINE_CACHE_CAPACITY = capacity
    while len(_ENGINE_CACHE) > _ENGINE_CACHE_CAPACITY:
        _ENGINE_CACHE.popitem(last=False)
        _ENGINE_CACHE_STATS["evictions"] += 1


def clear_engine_cache() -> None:
    _ENGINE_CACHE.clear()
    for k in _ENGINE_CACHE_STATS:
        _ENGINE_CACHE_STATS[k] = 0


def get_engine(
    *,
    eps: float,
    method: str = "log_factored",
    tol: float = 1e-6,
    max_iter: int = 2000,
    momentum: float = 1.0,
    use_pallas: Optional[bool] = None,
    inner_steps: Optional[int] = None,
    check_every: Optional[int] = None,
    precision: str = "highest",
) -> BatchedSinkhorn:
    """The cached :class:`BatchedSinkhorn` for a solver configuration.

    LRU semantics: a hit refreshes recency; a miss builds the engine and
    may evict the least-recently-used one (its jitted executables go with
    it). ``solve_many`` and the serving layer both come through here, so
    repeated calls never retrace — and distinct per-request configurations
    can no longer pin unbounded compile caches.
    """
    key = (method, float(eps), float(tol), int(max_iter), float(momentum),
           use_pallas, inner_steps, check_every, precision)
    engine = _ENGINE_CACHE.get(key)
    if engine is not None:
        _ENGINE_CACHE.move_to_end(key)
        _ENGINE_CACHE_STATS["hits"] += 1
        return engine
    _ENGINE_CACHE_STATS["misses"] += 1
    engine = BatchedSinkhorn(
        eps=eps, method=method, tol=tol, max_iter=max_iter,
        momentum=momentum, use_pallas=use_pallas, inner_steps=inner_steps,
        check_every=check_every, precision=precision,
    )
    _ENGINE_CACHE[key] = engine
    while len(_ENGINE_CACHE) > _ENGINE_CACHE_CAPACITY:
        _ENGINE_CACHE.popitem(last=False)
        _ENGINE_CACHE_STATS["evictions"] += 1
    return engine


_SHARDED_TWIN = {
    "factored": "sharded", "sharded": "sharded",
    "log_factored": "sharded_log", "sharded_log": "sharded_log",
    "auto": "auto",
}


def solve_many(
    problems: Sequence[OTProblem],
    *,
    method: str = "log_factored",
    eps: Optional[float] = None,
    tol: float = 1e-6,
    max_iter: int = 2000,
    momentum: float = 1.0,
    use_pallas: Optional[bool] = None,
    inner_steps: Optional[int] = None,
    check_every: Optional[int] = None,
    precision: str = "highest",
    mesh=None,
    mesh_axis: str = "data",
    f_inits: Optional[Sequence[Optional[jax.Array]]] = None,
    g_inits: Optional[Sequence[Optional[jax.Array]]] = None,
) -> List[SinkhornResult]:
    """Convenience wrapper: batched solve of a ragged problem list.

    ``eps`` defaults to the (shared) eps of the problems; mixed-eps lists
    are rejected — build one engine per eps instead. Engines (and hence
    their jitted vmapped solvers) are cached per configuration in a
    bounded LRU (:func:`get_engine`), so calling this in a loop does not
    retrace and distinct per-request configurations cannot leak compile
    caches without bound.

    ``f_inits``/``g_inits`` warm-start individual problems (per-problem
    potentials from an earlier solve; ``None`` entries cold-start) — see
    :meth:`BatchedSinkhorn.solve_many`.

    With ``mesh=`` each problem runs through the shard_map solver (the
    sharded twin of ``method``: scaling or psum'd-LSE log domain). Sharded
    problems are dispatched sequentially — each solve already occupies the
    whole mesh, so there is no idle hardware for a vmapped batch to fill.

    A sequence of :class:`~repro.core.spec.SolveSpec` is also accepted —
    the preferred form. The specs must share one
    method/tol/max_iter/momentum/policy (engines are per-configuration;
    heterogeneous configs go through ``solve(spec)`` one at a time); the
    solver kwargs above are then ignored except ``f_inits``/``g_inits``.
    """
    with obs.span("ot.solve_many"):
        if not problems:
            return []
        from .spec import SolveSpec  # lazy: spec imports this module

        if isinstance(problems[0], SolveSpec):
            specs: List[SolveSpec] = list(problems)
            head = specs[0]
            shared = (head.method, head.tol, head.max_iter, head.momentum,
                      head.policy, head.recovery)
            for s in specs:
                if not isinstance(s, SolveSpec):
                    raise TypeError(
                        "solve_many: mixed SolveSpec and OTProblem entries")
                if (s.method, s.tol, s.max_iter, s.momentum,
                        s.policy, s.recovery) != shared:
                    raise ValueError(
                        "solve_many(specs) needs one shared method/tol/"
                        "max_iter/momentum/policy/recovery across specs "
                        "(engines are per-configuration); call solve(spec) "
                        "per problem for heterogeneous configs")
                if s.schedule is not None or s.rank is not None \
                        or s.key is not None:
                    raise ValueError(
                        "solve_many(specs) does not support schedule/rank/"
                        "key; call solve(spec) per problem")
            pol = head.policy
            if pol.mesh is not None:
                if f_inits is not None or g_inits is not None:
                    raise ValueError(
                        "sharded solve_many dispatches sequentially; "
                        "per-problem warm starts are a batched-engine "
                        "feature — drop the mesh or the inits")
                twin = _SHARDED_TWIN.get(head.method)
                if twin is None:
                    raise ValueError(
                        f"solve_many(mesh=...) supports methods "
                        f"{sorted(_SHARDED_TWIN)}, got {head.method!r}")
                return [solve(s.replace(method=twin)) for s in specs]
            eps_set = {float(s.eps) for s in specs}
            if len(eps_set) != 1:
                raise ValueError(
                    f"mixed spec eps {sorted(eps_set)}; batched engines "
                    "are per-eps — group specs by eps")
            eng_method = ("log_factored" if head.method == "auto"
                          else head.method)
            with pol.scope():
                engine = get_engine(
                    eps=eps_set.pop(), method=eng_method, tol=head.tol,
                    max_iter=head.max_iter, momentum=head.momentum,
                    use_pallas=pol.use_pallas, inner_steps=pol.inner_steps,
                    check_every=pol.check_every, precision=pol.precision,
                )
                results = engine.solve_many([s.problem() for s in specs],
                                            f_inits=f_inits, g_inits=g_inits)
            if head.recovery is not None:
                # failed lanes climb the ladder INDIVIDUALLY (batched lanes
                # are independent under vmap — a diverged lane never poisons
                # its siblings, so only the failures pay for retries); the
                # already-computed lane result seeds the ladder so the base
                # configuration is not re-failed
                from ..resilience.health import classify
                from ..resilience.ladder import solve_with_recovery
                for i, r in enumerate(results):
                    fi = f_inits[i] if f_inits is not None else None
                    gi = g_inits[i] if g_inits is not None else None
                    h = classify(r, f_init=fi, g_init=gi,
                                 a=specs[i].problem().a, b=specs[i].problem().b)
                    if h.verdict not in head.recovery.accept:
                        results[i] = solve_with_recovery(
                            specs[i], first_attempt=r).result
            return results
        if (use_pallas is not None or inner_steps is not None
                or check_every is not None or precision != "highest"):
            warnings.warn(
                "passing execution kwargs (use_pallas=/inner_steps=/"
                "check_every=/precision=) to solve_many() directly is "
                "deprecated: build SolveSpecs with a shared ExecutionPolicy "
                "(repro.core.spec) and call solve_many(specs)",
                DeprecationWarning, stacklevel=2)
        eps_set = {float(p.eps) for p in problems}
        if eps is None:
            if len(eps_set) != 1:
                raise ValueError(f"mixed problem eps {sorted(eps_set)}; pass eps=")
            eps = eps_set.pop()
        if mesh is not None:
            if f_inits is not None or g_inits is not None:
                raise ValueError(
                    "solve_many(mesh=...) dispatches problems sequentially "
                    "through solve(); per-problem warm starts are a batched-"
                    "engine feature — drop mesh= or the inits"
                )
            twin = _SHARDED_TWIN.get(method)
            if twin is None:
                raise ValueError(
                    f"solve_many(mesh=...) supports methods "
                    f"{sorted(_SHARDED_TWIN)}, got {method!r}"
                )
            # every execution knob passes through: use_pallas picks the
            # per-shard fused log plan, inner_steps reaches the sharded
            # runner's megakernel refusal instead of being dropped
            return [
                solve(p.__class__(p.geometry.rebuild_at(eps), p.a, p.b),
                      method=twin, tol=tol, max_iter=max_iter,
                      momentum=momentum, mesh=mesh, mesh_axis=mesh_axis,
                      use_pallas=use_pallas, inner_steps=inner_steps,
                      check_every=check_every, precision=precision)
                for p in problems
            ]
        engine = get_engine(
            eps=eps, method=method, tol=tol, max_iter=max_iter,
            momentum=momentum, use_pallas=use_pallas, inner_steps=inner_steps,
            check_every=check_every, precision=precision,
        )
        return engine.solve_many(problems, f_inits=f_inits, g_inits=g_inits)
