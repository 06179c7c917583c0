"""Sinkhorn solvers: factored (linear-time), quadratic baseline, log-domain.

Algorithm 1 of the paper, generic in the kernel *operator*:

    repeat:  v <- b / K^T u ;  u <- a / K v
    until || v . (K^T u) - b ||_1 < tol

The factored path applies K = Xi @ Zeta^T as two thin matmuls — O(r(n+m))
per iteration. The loop is a ``lax.while_loop`` (non-differentiable on
purpose; gradients flow through the envelope theorem in ``grad.py``).

This module is organised as operator-generic BUILDING BLOCKS that every
solver in the repo composes:

  * ``make_scaling_step``   — one full scaling-space iteration (u, v, s)
  * ``make_log_step``       — one full log-domain iteration (f, g)
  * ``factored_log_matvecs``/``dense_log_matvecs`` — the log-space kernel
    operators shared with ``accelerated.py`` and ``api.py``
  * ``run_marginal_loop``   — the tol/max_iter while_loop shared by all

``api.solve`` and the ``BatchedSinkhorn`` engine (``api.py``) vmap these
blocks over a leading batch axis; ``sharded.py`` composes the same scaling
step with psum'd contractions inside ``shard_map``.

``sinkhorn_geometry`` / ``sinkhorn_log_geometry`` additionally accept
``use_pallas``: when the geometry declares a fused Pallas plan
(``Geometry.pallas_ops`` -> ``kernels.ops.geometry_ops``), the
``lax.while_loop`` body runs through the plan's fused kernels (feature
contraction + half-step with the marginal divide/subtract fused) instead
of the XLA operators — auto-on on TPU backends, opt-in interpret mode in
tests, elementwise-identical semantics either way.

Implementation notes
--------------------
* We reuse ``s = K^T u`` across the marginal check and the next v-update,
  so convergence monitoring is free (one matvec + one rmatvec per iter).
* Every solver ends on a **u-update**, so the row marginals are exact and
  the dual value collapses to  W_hat = eps (a . log u + b . log v) (Eq. 6).
* ``momentum`` in (1, 2) enables over-relaxed Sinkhorn (Thibault et al.),
  the cheap acceleration alternative to the paper's Remark-2 AGM variant.
* Log-domain solvers operate on (f, g) = eps (log u, log v) and use an
  exact two-stage logsumexp for the factored kernel (all entries positive):
      t_k       = LSE_i( logXi[i,k] + f_i / eps )
      (log K^T e^{f/eps})_j = LSE_k( logZeta[j,k] + t_k )
* Zero-weight atoms are SUPPORTED: a_i = 0 (resp. b_j = 0) atoms get
  u_i = 0 / f_i = -inf and are excluded from the masked dual value. This is
  what makes bucket-padding in the batched engine exact rather than
  approximate — padded atoms carry zero mass and change nothing.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import obs
from ..kernels.backend import resolve_backend
from ..kernels.ops import (
    check_precision,
    geometry_ops,
    relax_log,
    relax_scaling,
)
from .geometry import (
    DenseCost, FactoredPositive, Geometry, _masked_log, _matmul,
)

__all__ = [
    "SinkhornResult",
    "geometry_reduce",
    "make_scaling_step",
    "make_log_step",
    "factored_log_matvecs",
    "dense_log_matvecs",
    "run_marginal_loop",
    "masked_dual_value",
    "sinkhorn_operator",
    "sinkhorn_geometry",
    "sinkhorn_log_geometry",
    "sinkhorn_factored",
    "sinkhorn_quadratic",
    "sinkhorn_log_factored",
    "sinkhorn_log_quadratic",
    "dual_objective",
]


class SinkhornResult(NamedTuple):
    """Solver output. ``u``/``v`` are scalings; ``f``/``g`` potentials."""

    u: jax.Array
    v: jax.Array
    f: jax.Array            # eps * log u
    g: jax.Array            # eps * log v
    cost: jax.Array         # W_hat = eps (a.log u + b.log v)   (Eq. 6)
    n_iter: jax.Array
    marginal_err: jax.Array
    converged: jax.Array

    @property
    def diverged(self) -> jax.Array:
        """Structured divergence flag: the iteration blew up (non-finite
        marginal error or dual value) rather than merely not converging
        yet. This is how the signed-Nystrom small-eps failure mode (paper
        Figs. 1/3/5) is surfaced — ``converged=False, diverged=True`` —
        instead of handing callers raw NaNs to interpret. Implemented as a
        property so the pytree structure (vmap / shard_map out_specs) is
        unchanged."""
        return ~(jnp.isfinite(self.marginal_err) & jnp.isfinite(self.cost))

    @property
    def health(self):
        """Host-side :class:`~repro.resilience.health.SolveHealth` verdict
        for a CONCRETE unbatched result (``ok`` / ``maxed_out`` /
        ``diverged``). Pulls the scalar diagnostics to host — inside
        ``jit``/``vmap`` use :attr:`diverged`, which stays an array. The
        ``poisoned_warm_start`` verdict needs the warm-start context the
        result alone does not carry; classify through
        :func:`repro.resilience.classify` with ``f_init``/``g_init``
        to enable it."""
        from ..resilience.health import classify  # lazy: avoid cycle
        return classify(self)


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------


def masked_dual_value(a, b, f, g, reduce: Callable = jnp.sum):
    """W_hat = <a, f> + <b, g> with zero-weight atoms excluded.

    Padded atoms have a_i = 0 and f_i = -inf; a plain vdot would produce
    0 * -inf = nan, so both terms mask on strictly positive weight.
    ``reduce`` lets SPMD callers psum the local partial sums so the value
    replicates across devices (see :func:`geometry_reduce`).
    """
    ta = reduce(jnp.where(a > 0, a * f, 0.0))
    tb = reduce(jnp.where(b > 0, b * g, 0.0))
    return ta + tb


def geometry_reduce(geom: "Geometry") -> Callable[[jax.Array], jax.Array]:
    """The scalar-reduction hook a geometry's execution mode implies.

    Single-device geometries reduce with a plain ``jnp.sum``; row-sharded
    wrappers (``geom.spmd_axis`` set) additionally psum over the mesh axis
    so the marginal error driving the while_loop and the dual value are
    REPLICATED — every device exits the loop together (an SPMD
    requirement) and the cost needs no post-hoc collective.
    """
    ax = geom.spmd_axis
    if ax is None:
        return jnp.sum
    return lambda e: jax.lax.psum(jnp.sum(e), ax)


def make_scaling_step(
    matvec: Callable[[jax.Array], jax.Array],
    rmatvec: Callable[[jax.Array], jax.Array],
    a: jax.Array,
    b: jax.Array,
    *,
    momentum: float = 1.0,
    err_reduce: Callable[[jax.Array], jax.Array] = jnp.sum,
):
    """One full Alg.-1 iteration in scaling space.

    Returns ``step((u, v, s)) -> ((u', v', s'), err)`` where ``s = K^T u``
    is carried so the marginal check is free. ``err_reduce`` lets SPMD
    callers (``sharded.py``) psum the local L1 error into a replicated
    scalar.
    """

    def step(carry):
        u, v, s = carry
        # geometric over-relaxation: u <- u_old^{1-w} * u_new^{w}.
        # Dead (zero-mass) atoms are pinned to scaling 0 rather than left
        # to b/s: a stale kernel row under a dead slot can underflow its
        # contraction to exactly 0, and the resulting 0/0 = NaN would ride
        # the next matvec into every LIVE lane.
        v_new = relax_scaling(jnp.where(b > 0, b / s, 0.0), v, momentum)
        u_new = relax_scaling(jnp.where(a > 0, a / matvec(v_new), 0.0),
                              u, momentum)
        s_new = rmatvec(u_new)
        err = err_reduce(jnp.abs(v_new * s_new - b))
        return (u_new, v_new, s_new), err

    return step


def factored_log_matvecs(
    log_xi: jax.Array, log_zeta: jax.Array, *, eps: float
) -> Tuple[Callable, Callable]:
    """Exact two-stage LSE operators for K = Xi Zeta^T (all entries > 0).

        log_matvec(g)  = log(K   e^{g/eps})   (n,)
        log_rmatvec(f) = log(K^T e^{f/eps})   (m,)

    Cost O(r (n + m)) each. Thin wrapper over the
    :class:`~repro.core.geometry.FactoredPositive` geometry's operators —
    the single source of truth for the factored log-matvec math.
    """
    geom = FactoredPositive(log_xi=log_xi, log_zeta=log_zeta, eps=eps)
    return geom.log_operators()


def dense_log_matvecs(C: jax.Array, *, eps: float) -> Tuple[Callable, Callable]:
    """Dense O(nm) log-operators on the Gibbs kernel of cost matrix C
    (the :class:`~repro.core.geometry.DenseCost` geometry's operators)."""
    geom = DenseCost(C, eps)
    return geom.log_operators()


def make_log_step(
    log_matvec: Callable[[jax.Array], jax.Array],
    log_rmatvec: Callable[[jax.Array], jax.Array],
    a: jax.Array,
    b: jax.Array,
    *,
    eps: float,
    momentum: float = 1.0,
    err_reduce: Callable[[jax.Array], jax.Array] = jnp.sum,
):
    """One full log-domain iteration: ``step((f, g)) -> ((f', g'), err)``.

    ``momentum`` in (1, 2) applies the log-space over-relaxation
    ``f <- (1-w) f_old + w f_new`` — the exact log of the geometric
    relaxation in :func:`make_scaling_step` (-inf potentials of zero-weight
    atoms bypass the blend).
    """
    loga, logb = _masked_log(a), _masked_log(b)

    def step(carry):
        f, g = carry
        g = relax_log(eps * (logb - log_rmatvec(f)), g, momentum)
        f = relax_log(eps * (loga - log_matvec(g)), f, momentum)
        log_col = log_rmatvec(f) + g / eps       # log of column marginal
        err = err_reduce(jnp.abs(jnp.exp(log_col) - b))
        return (f, g), err

    return step


def run_marginal_loop(step, carry0, *, tol: float, max_iter: int, dtype,
                      steps_per_check: int = 1, iters_per_step: int = 1):
    """Run ``step`` until the marginal error drops below ``tol``.

    One mandatory check block is always taken (so e.g. u.Kv = 1 holds for
    the Eq.-6 dual shortcut). Returns ``(n_iter, carry, err)``.

    Cadence semantics (``check_every`` at the solver surface):
    ``steps_per_check`` step calls run back to back (Python-unrolled, so
    the intermediate error computations are dead code XLA eliminates)
    before each convergence check, and each step call itself advances
    ``iters_per_step`` iterations (1 for the per-iteration steps,
    ``inner_steps`` for the fused megakernel block step). The loop
    therefore checks the error — and a distributed run synchronizes on the
    replicated scalar — once every ``steps_per_check * iters_per_step``
    iterations; the result still satisfies ``err <= tol`` on convergence,
    but ``n_iter`` is a multiple of the cadence and ``max_iter`` is
    effectively rounded UP to the next multiple (a block that starts
    before the cap runs to completion). A divergence (non-finite error)
    inside a block is likewise detected at its boundary — NaN/inf iterates
    propagate, they never un-poison.

    Distribution hook: the loop itself is SPMD-agnostic — under
    ``shard_map`` the step's ``err_reduce`` (see :func:`geometry_reduce`)
    psums the error, so the while_loop carries a REPLICATED scalar and
    every device exits at the same iteration (no control-flow divergence).

    Observability: the ``ot.loop`` span covers the eager first block and
    the while_loop's trace, lowering, compile (or cache read) and dispatch;
    ``cond`` counts ``ot.loop.traces`` as it is traced (``body`` also runs
    once eagerly, ``cond`` does not), so a solve that re-traces its loop
    adds one a call and a cached one adds none.
    """
    cadence = steps_per_check * iters_per_step

    def body(state):
        it, carry, err = state
        for _ in range(steps_per_check):
            carry, err = step(carry)
        return it + cadence, carry, err

    def cond(state):
        obs.count("ot.loop.traces")
        it, _, err = state
        return (it < max_iter) & (err > tol) & jnp.isfinite(err)

    with obs.span("ot.loop"):
        state0 = body((jnp.array(0, jnp.int32), carry0,
                       jnp.asarray(jnp.inf, dtype)))
        return jax.lax.while_loop(cond, body, state0)


# ---------------------------------------------------------------------------
# Fused Pallas plan selection (the use_pallas policy)
# ---------------------------------------------------------------------------


def _maybe_pallas_plan(geom: Geometry, use_pallas: Optional[bool],
                       mode: str, precision: str = "highest"):
    """Resolve the ``use_pallas`` policy into a fused plan (or ``None``).

    ``None`` (auto) turns the fused path on exactly when the resolved
    execution backend COMPILES its Pallas lowering (tpu-mosaic AND
    gpu-triton — see ``kernels.backend``); interpret-only platforms keep
    the XLA operators. ``True`` forces the plan (interpret mode on CPU —
    the test configuration), ``False`` forces the XLA operators.
    Geometries without a fused plan (dense, Nystrom, grids) always fall
    back. :func:`_plan_loop` reports the selection through the
    ``repro.obs.observe_plan_selection`` hook.

    A row-sharded geometry (``spmd_axis`` set) gets the per-shard log plan,
    which combines each stage-1 LSE across the axis before the half-step
    that reads it; in scaling mode it has none.
    """
    if use_pallas is None:
        use_pallas = not resolve_backend().interpret
    if not use_pallas:
        return None
    return geometry_ops(geom, mode=mode, precision=precision)


def _resolve_cadence(plan, inner_steps: Optional[int],
                     check_every: Optional[int]):
    """Resolve the ``inner_steps`` / ``check_every`` knobs into concrete
    (inner, check) iteration counts.

    Auto policy (both ``None``): when the fused plan COMPILES (TPU) and
    offers a megakernel block step, run 8 iterations per launch and check
    convergence once per block; everywhere else keep today's
    check-every-iteration semantics (interpret-mode megakernels are a
    test/bench configuration, never an auto win). Explicit values are
    honored on every path — on the XLA operators ``inner_steps`` degrades
    to the same check cadence (unrolled steps, fewer error reductions and
    loop syncs), which is the documented fallback semantics.
    """
    auto = inner_steps is None and check_every is None
    if auto:
        if plan is not None and not plan.interpret \
                and plan.make_block_step is not None:
            return 8, 8, True
        return 1, 1, True
    inner = 1 if inner_steps is None else int(inner_steps)
    if inner < 1:
        raise ValueError(f"inner_steps must be >= 1, got {inner_steps}")
    check = inner if check_every is None else int(check_every)
    if check < 1:
        raise ValueError(f"check_every must be >= 1, got {check_every}")
    if check % inner != 0:
        raise ValueError(
            f"check_every ({check}) must be a multiple of inner_steps "
            f"({inner}): the marginal error only exists at megakernel "
            "block boundaries"
        )
    return inner, check, False


def _plan_loop(plan, step_args, *, geometry, tol, max_iter, dtype,
               inner_steps, check_every, momentum,
               reduce: Callable = jnp.sum):
    """Shared hot-loop driver for both fused-plan modes: resolve the
    cadence, prefer the persistent megakernel block step (``inner_steps``
    iterations per launch, carries on-chip), fall back to the streaming
    per-iteration step at the same check cadence. Reports the selection
    (``step`` = "megakernel" | "per_iteration", ``axis`` = the plan's mesh
    axis or ``None``) to the plan hook. ``reduce`` sums the per-iteration
    step's marginal error (:func:`geometry_reduce`)."""
    a, b = step_args
    inner, check, auto = _resolve_cadence(plan, inner_steps, check_every)
    block = None
    if inner > 1 and plan.make_block_step is not None:
        block = plan.make_block_step(a, b, inner_steps=inner,
                                     momentum=momentum)
    obs.notify_plan_selected({
        "geometry": geometry,
        "mode": plan.mode,
        "kind": plan.kind,
        "precision": plan.precision,
        "interpret": plan.interpret,
        "step": "per_iteration" if block is None else "megakernel",
        "axis": plan.axis,
    })
    if block is not None:
        step, init = block
        return init, functools.partial(
            run_marginal_loop, step, tol=tol, max_iter=max_iter,
            dtype=dtype, steps_per_check=check // inner,
            iters_per_step=inner,
        )
    # no megakernel at this shape/budget: auto keeps the exact
    # per-iteration semantics; explicit knobs keep the check cadence
    # (unrolled steps) so iteration-count semantics stay identical
    step, init = plan.make_step(a, b, momentum=momentum, err_reduce=reduce)
    return init, functools.partial(
        run_marginal_loop, step, tol=tol, max_iter=max_iter, dtype=dtype,
        steps_per_check=1 if auto else check,
    )


def _finish_scaling(a, b, u, v, it, err, *, eps, tol,
                    reduce: Callable = jnp.sum) -> SinkhornResult:
    with obs.span("ot.finish"):
        f, g = eps * _masked_log(u), eps * _masked_log(v)
        cost = masked_dual_value(a, b, f, g, reduce)
        return SinkhornResult(u, v, f, g, cost, it, err, err <= tol)


def _solve_scaling_plan(plan, a, b, *, geometry, eps, tol, max_iter,
                        momentum, u_init, inner_steps=None,
                        check_every=None) -> SinkhornResult:
    """Alg. 1 with the ``lax.while_loop`` body routed through the fused
    Pallas plan — semantics (masking, warm start, marginal check, momentum)
    identical to :func:`sinkhorn_operator` up to the check cadence
    (``inner_steps`` iterations per megakernel launch, error at block
    boundaries)."""
    n, m = a.shape[0], b.shape[0]
    dtype = a.dtype
    u0 = jnp.ones((n,), dtype) if u_init is None else u_init
    v0 = jnp.ones((m,), dtype)
    init, loop = _plan_loop(
        plan, (a, b), geometry=geometry, tol=tol, max_iter=max_iter,
        dtype=dtype, inner_steps=inner_steps, check_every=check_every,
        momentum=momentum,
    )
    it, (u, v, _), err = loop(init(u0, v0))
    return _finish_scaling(a, b, u, v, it, err, eps=eps, tol=tol)


# ---------------------------------------------------------------------------
# Scaling-space solvers
# ---------------------------------------------------------------------------


def sinkhorn_operator(
    matvec: Callable[[jax.Array], jax.Array],      # v (m,) -> K v (n,)
    rmatvec: Callable[[jax.Array], jax.Array],     # u (n,) -> K^T u (m,)
    a: jax.Array,
    b: jax.Array,
    *,
    eps: float,
    tol: float = 1e-6,
    max_iter: int = 2000,
    momentum: float = 1.0,
    u_init: Optional[jax.Array] = None,
    err_reduce: Callable[[jax.Array], jax.Array] = jnp.sum,
    check_every: int = 1,
) -> SinkhornResult:
    """Algorithm 1 on an abstract positive kernel operator.

    ``err_reduce`` is the SPMD hook: sharded callers pass the psum'd
    reduction of :func:`geometry_reduce` so the convergence scalar (and
    the dual value) replicate across devices. ``check_every`` sets the
    convergence-check cadence (see :func:`run_marginal_loop`): iteration
    counts become multiples of it, the converged result still satisfies
    ``err <= tol``.
    """
    n, m = a.shape[0], b.shape[0]
    dtype = a.dtype
    u0 = jnp.ones((n,), dtype) if u_init is None else u_init
    v0 = jnp.ones((m,), dtype)
    step = make_scaling_step(matvec, rmatvec, a, b, momentum=momentum,
                             err_reduce=err_reduce)
    it, (u, v, _), err = run_marginal_loop(
        step, (u0, v0, rmatvec(u0)), tol=tol, max_iter=max_iter,
        dtype=dtype, steps_per_check=int(check_every),
    )
    return _finish_scaling(a, b, u, v, it, err, eps=eps, tol=tol,
                           reduce=err_reduce)


def sinkhorn_geometry(
    geom: Geometry,
    a: jax.Array,
    b: jax.Array,
    *,
    tol: float = 1e-6,
    max_iter: int = 2000,
    momentum: float = 1.0,
    u_init: Optional[jax.Array] = None,
    use_pallas: Optional[bool] = None,
    inner_steps: Optional[int] = None,
    check_every: Optional[int] = None,
    precision: str = "highest",
) -> SinkhornResult:
    """Algorithm 1 in scaling space on any Geometry's native operators.

    This is the one scaling-space entry point every cost family shares:
    factored kernels get O(r(n+m)) iterations, grids get axis-wise
    convolutions, dense costs get the O(nm) baseline, and signed Nystrom
    factors run (and possibly diverge — see ``SinkhornResult.diverged``)
    without any representation branching at the call site.

    ``use_pallas`` selects between the geometry's HOISTED XLA operators
    and the fused Pallas plan (``kernels.ops.geometry_ops``) for the
    while_loop body: ``None`` auto-enables the plan on TPU backends only,
    ``True`` forces it (interpret mode off-TPU — the test path), ``False``
    forces the XLA operators. Either way per-family precomputation (dense
    Gibbs kernel, feature materialization, per-axis grid kernels) happens
    once per solve, not inside the while_loop.

    ``inner_steps`` fuses that many full iterations into ONE persistent
    megakernel launch (``kernels.fused_loop``) when the plan offers one
    (factors VMEM-resident, scalings on-chip, marginal error only at
    block boundaries); ``check_every`` sets the convergence-check cadence
    in iterations (a multiple of ``inner_steps``). Both default to an
    auto policy — 8/8 on compiled (TPU) fused plans whose working set
    fits VMEM, today's 1/1 semantics everywhere else; on the XLA
    operators an explicit ``inner_steps`` degrades to the same check
    cadence. Iteration counts become multiples of the cadence; converged
    results still satisfy ``err <= tol``. ``precision="bf16"`` stores and
    streams the kernel factors at half width with f32 accumulation (the
    mixed-precision execution policy).
    """
    check_precision(precision)
    with obs.span("ot.featurize"):
        plan = _maybe_pallas_plan(geom, use_pallas, "scaling", precision)
        if plan is None:
            _, check, _ = _resolve_cadence(None, inner_steps, check_every)
            matvec, rmatvec = geom.operators(precision=precision)
    if plan is not None:
        return _solve_scaling_plan(
            plan, a, b, geometry=type(geom).__name__, eps=geom.eps,
            tol=tol, max_iter=max_iter, momentum=momentum, u_init=u_init,
            inner_steps=inner_steps, check_every=check_every,
        )
    return sinkhorn_operator(
        matvec, rmatvec, a, b, eps=geom.eps, tol=tol,
        max_iter=max_iter, momentum=momentum, u_init=u_init,
        err_reduce=geometry_reduce(geom), check_every=check,
    )


def sinkhorn_factored(
    xi: jax.Array,          # (n, r) strictly positive features of mu's support
    zeta: jax.Array,        # (m, r) strictly positive features of nu's support
    a: jax.Array,
    b: jax.Array,
    *,
    eps: float,
    tol: float = 1e-6,
    max_iter: int = 2000,
    momentum: float = 1.0,
    u_init: Optional[jax.Array] = None,
) -> SinkhornResult:
    """Linear-time Sinkhorn on K = xi @ zeta.T (the paper's Section 3.1)."""
    return sinkhorn_geometry(
        FactoredPositive(xi=xi, zeta=zeta, eps=eps), a, b, tol=tol,
        max_iter=max_iter, momentum=momentum, u_init=u_init,
    )


def sinkhorn_quadratic(
    K: jax.Array,           # (n, m) dense positive Gibbs kernel
    a: jax.Array,
    b: jax.Array,
    *,
    eps: float,
    tol: float = 1e-6,
    max_iter: int = 2000,
    momentum: float = 1.0,
    u_init: Optional[jax.Array] = None,
) -> SinkhornResult:
    """The paper's ``Sin`` baseline (Cuturi '13): dense O(nm) matvecs."""
    return sinkhorn_operator(
        lambda v: _matmul(K, v), lambda u: _matmul(K.T, u), a, b,
        eps=eps, tol=tol, max_iter=max_iter, momentum=momentum, u_init=u_init,
    )


# ---------------------------------------------------------------------------
# Log-domain (small-eps safe)
# ---------------------------------------------------------------------------


def sinkhorn_log_geometry(
    geom: Geometry,
    a: jax.Array,
    b: jax.Array,
    *,
    tol: float = 1e-6,
    max_iter: int = 2000,
    momentum: float = 1.0,
    f_init: Optional[jax.Array] = None,
    g_init: Optional[jax.Array] = None,
    use_pallas: Optional[bool] = None,
    inner_steps: Optional[int] = None,
    check_every: Optional[int] = None,
    precision: str = "highest",
) -> SinkhornResult:
    """Log-domain (small-eps safe) Sinkhorn on any log-capable Geometry.

    The geometry supplies its hoisted ``log_operators()`` — exact
    two-stage LSE for positive-factored families, axis-wise log-convolution
    for grids, dense LSE for explicit costs. ``f_init``/``g_init``
    warm-start the potentials (epsilon annealing); ``momentum`` applies the
    log-space over-relaxation of :func:`make_log_step`. ``use_pallas``
    routes the while_loop body through the fused log-feature Pallas plan
    (``kernels.ops.geometry_ops(mode="log")``) — auto-on when the backend
    compiles Pallas (TPU), opt-in interpret mode otherwise.

    ``inner_steps`` / ``check_every`` / ``precision`` are the log-domain
    twins of the :func:`sinkhorn_geometry` knobs: a persistent log
    megakernel block (potentials + stage-1 LSE carry on-chip), the
    convergence-check cadence (iteration counts become multiples of it),
    and bf16 log-feature storage with f32 LSE accumulation.
    """
    check_precision(precision)
    with obs.span("ot.featurize"):
        plan = _maybe_pallas_plan(geom, use_pallas, "log", precision)
        if plan is None:
            _, check, _ = _resolve_cadence(None, inner_steps, check_every)
            log_matvec, log_rmatvec = geom.log_operators(precision=precision)
    if plan is not None:
        return _solve_log_plan(
            plan, a, b, geometry=type(geom).__name__, eps=geom.eps,
            tol=tol, max_iter=max_iter,
            momentum=momentum, f_init=f_init, g_init=g_init,
            inner_steps=inner_steps, check_every=check_every,
            reduce=geometry_reduce(geom),
        )
    return _log_domain_solve(
        log_matvec, log_rmatvec, a, b, eps=geom.eps, tol=tol,
        max_iter=max_iter, momentum=momentum, f_init=f_init, g_init=g_init,
        err_reduce=geometry_reduce(geom), check_every=check,
    )


def _log_init(a, b, f_init, g_init):
    """Initial potentials, with zero-weight atoms pinned to -inf.

    The pin makes padding exact from ITERATION 0, not just at the fixed
    point: a dead atom's exp(-inf + ...) contributes nothing to the very
    first LSE, so a bucket/shard-padded solve's live iterates equal the
    unpadded solve's elementwise. (The iteration forces dead atoms to
    -inf after one step anyway — this just removes the transient.)
    Warm starts from a previous masked solve already carry -inf there,
    so the mask is idempotent.
    """
    n, m = a.shape[0], b.shape[0]
    dtype = a.dtype
    f0 = jnp.zeros((n,), dtype) if f_init is None else f_init
    g0 = jnp.zeros((m,), dtype) if g_init is None else g_init
    f0 = jnp.where(a > 0, f0, -jnp.inf)
    g0 = jnp.where(b > 0, g0, -jnp.inf)
    return f0, g0, dtype


def _finish_log(a, b, f, g, it, err, *, eps, tol,
                reduce: Callable = jnp.sum) -> SinkhornResult:
    with obs.span("ot.finish"):
        cost = masked_dual_value(a, b, f, g, reduce)
        u, v = jnp.exp(f / eps), jnp.exp(g / eps)
        return SinkhornResult(u, v, f, g, cost, it, err, err <= tol)


def _log_domain_solve(
    log_matvec, log_rmatvec, a, b, *, eps, tol, max_iter, momentum=1.0,
    f_init=None, g_init=None,
    err_reduce: Callable[[jax.Array], jax.Array] = jnp.sum,
    check_every: int = 1,
) -> SinkhornResult:
    f0, g0, dtype = _log_init(a, b, f_init, g_init)
    step = make_log_step(log_matvec, log_rmatvec, a, b, eps=eps,
                         momentum=momentum, err_reduce=err_reduce)
    it, (f, g), err = run_marginal_loop(
        step, (f0, g0), tol=tol, max_iter=max_iter, dtype=dtype,
        steps_per_check=int(check_every),
    )
    return _finish_log(a, b, f, g, it, err, eps=eps, tol=tol,
                       reduce=err_reduce)


def _solve_log_plan(plan, a, b, *, geometry, eps, tol, max_iter, momentum,
                    f_init, g_init, inner_steps=None, check_every=None,
                    reduce: Callable = jnp.sum) -> SinkhornResult:
    """Log-domain solve with the while_loop body routed through the fused
    log-feature Pallas plan — semantics identical to
    :func:`_log_domain_solve` (same iterates, masking, warm starts) up to
    the check cadence. ``reduce`` (:func:`geometry_reduce`) sums the
    marginal error and the dual value: under ``shard_map`` it psums them,
    so every device exits the loop together and returns the whole cost."""
    f0, g0, dtype = _log_init(a, b, f_init, g_init)
    init, loop = _plan_loop(
        plan, (a, b), geometry=geometry, tol=tol, max_iter=max_iter,
        dtype=dtype, inner_steps=inner_steps, check_every=check_every,
        momentum=momentum, reduce=reduce,
    )
    it, (f, g, _), err = loop(init(f0, g0))
    return _finish_log(a, b, f, g, it, err, eps=eps, tol=tol, reduce=reduce)


def sinkhorn_log_factored(
    log_xi: jax.Array,      # (n, r) log-features
    log_zeta: jax.Array,    # (m, r)
    a: jax.Array,
    b: jax.Array,
    *,
    eps: float,
    tol: float = 1e-6,
    max_iter: int = 2000,
    f_init: Optional[jax.Array] = None,
    g_init: Optional[jax.Array] = None,
) -> SinkhornResult:
    """Log-stabilized linear Sinkhorn via exact two-stage logsumexp.

    Positivity of the factored kernel makes the split LSE *exact*:
        log (K^T e^{f/eps})_j = LSE_k( logZeta_jk + LSE_i(logXi_ik + f_i/eps) ).
    Cost O(r (n + m)) per iteration, identical to the scaling-space path.
    ``f_init``/``g_init`` warm-start the potentials (epsilon annealing).
    """
    log_matvec, log_rmatvec = factored_log_matvecs(log_xi, log_zeta, eps=eps)
    return _log_domain_solve(
        log_matvec, log_rmatvec, a, b, eps=eps, tol=tol, max_iter=max_iter,
        f_init=f_init, g_init=g_init,
    )


def sinkhorn_log_quadratic(
    C: jax.Array,           # (n, m) cost matrix
    a: jax.Array,
    b: jax.Array,
    *,
    eps: float,
    tol: float = 1e-6,
    max_iter: int = 5000,
    f_init: Optional[jax.Array] = None,
    g_init: Optional[jax.Array] = None,
) -> SinkhornResult:
    """Dense log-domain Sinkhorn — the ground-truth oracle for benchmarks."""
    log_matvec, log_rmatvec = dense_log_matvecs(C, eps=eps)
    return _log_domain_solve(
        log_matvec, log_rmatvec, a, b, eps=eps, tol=tol, max_iter=max_iter,
        f_init=f_init, g_init=g_init,
    )


def dual_objective(
    f: jax.Array, g: jax.Array, a: jax.Array, b: jax.Array,
    K_apply: Callable[[jax.Array], jax.Array], *, eps: float
) -> jax.Array:
    """a.f + b.g - eps <e^{f/eps}, K e^{g/eps}> + eps   (Eq. 5)."""
    u, v = jnp.exp(f / eps), jnp.exp(g / eps)
    return jnp.vdot(a, f) + jnp.vdot(b, g) - eps * jnp.vdot(u, K_apply(v)) + eps
