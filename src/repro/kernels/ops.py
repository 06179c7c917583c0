"""Jitted public wrappers over the Pallas kernels + the fused solve plans.

Execution policy is a first-class :class:`~repro.kernels.backend.Backend`
record (``kernels.backend.resolve_backend``): tpu-mosaic compiles the
sequential-grid kernels as written; gpu-triton compiles too but routes
grid reductions through their split-k variants and admission-gates the
megakernel at shared-memory size; only platforms with no compiled lowering
interpret. Every wrapper accepts ``backend=`` (record or name, resolved
upstream or here); ``backend="interpret"`` is the test configuration (the
legacy ``interpret=`` bool kwarg is gone).

``fused_sinkhorn_iteration`` composes the kernels into one full Alg.-1
iteration (v then u) — this is the paper's O(r(n+m)) hot loop as it would
run on hardware.

``geometry_ops`` is the consumer of the Geometry layer's ``pallas_ops()``
hook: the GEOMETRY decides which fused kernels apply to its cost family
(fused Lemma-1 feature map + feature_contract + half-step for Gaussian
point clouds, feature_contract + half-step for explicit factors, the LSE
twins for log-features), and call sites just ask for the plan instead of
hard-coding a kernel choice. The returned :class:`GeometryOps` carries,
besides the canonical fused ``iteration``, a ``make_step`` builder whose
step is drop-in compatible with ``core.sinkhorn.run_marginal_loop`` — that
is how ``sinkhorn_geometry`` / ``sinkhorn_log_geometry`` route their
``lax.while_loop`` hot loop through the fused kernels (``use_pallas``).

``observe_plan_selection`` is the test hook (``repro.obs``, re-exported
here): while the context is active, every fused-plan selection on a solve
path appends an event dict, so tests can assert the hot loop really ran
through the plan.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..distributed.sharding import psum_logsumexp
from ..obs import _PLAN_OBSERVERS  # noqa: F401  (the plan hook lives there)
from ..obs import notify_plan_selected, observe_plan_selection
from .backend import Backend, fused_map_admissible, resolve_backend
from .feature_map import gaussian_feature_map_pallas
from .fused_loop import (
    block_plan_fits,
    log_sinkhorn_block_pallas,
    relax_log,
    relax_scaling,
    sinkhorn_block_pallas,
)
from .kermatvec import (
    feature_contract_pallas,
    feature_matvec_pallas,
    sinkhorn_halfstep_pallas,
)
from .logmatvec import (
    log_feature_contract_pallas,
    log_halfstep_pallas,
    log_matvec_pallas,
)
from .paged import (
    paged_feature_contract_pallas,
    paged_feature_matvec_pallas,
    paged_halfstep_pallas,
    paged_supported,
)
from .ref import gaussian_feature_map_ref

__all__ = [
    "gaussian_feature_map",
    "feature_contract",
    "feature_matvec",
    "sinkhorn_halfstep",
    "log_matvec",
    "log_feature_contract",
    "log_halfstep",
    "fused_sinkhorn_iteration",
    "fused_log_sinkhorn_iteration",
    "batched_sinkhorn_halfstep",
    "fused_batched_sinkhorn_iteration",
    "relax_scaling",
    "relax_log",
    "PRECISIONS",
    "check_precision",
    "GeometryOps",
    "geometry_ops",
    "observe_plan_selection",
    "notify_plan_selected",
]


# ---------------------------------------------------------------------------
# Thin backend-resolving wrappers
# ---------------------------------------------------------------------------


def gaussian_feature_map(
    x: jax.Array,
    anchors: jax.Array,
    log_const: jax.Array,
    *,
    inv_eps: float,
    log_space: bool = False,
    backend: Optional[Backend] = None,
) -> jax.Array:
    be = resolve_backend(backend)
    if not fused_map_admissible(x.shape[1], be):
        # the fused map's d axis is a sequential accumulation grid; when it
        # cannot ride in one block on a parallel-grid backend, REFUSE into
        # the streaming XLA map — never silently interpret.
        return gaussian_feature_map_ref(
            x, anchors, log_const, inv_eps=inv_eps, log_space=log_space)
    return gaussian_feature_map_pallas(
        x, anchors, log_const, inv_eps=inv_eps, interpret=be.interpret,
        log_space=log_space, backend=be,
    )


def feature_contract(
    xi: jax.Array, u: jax.Array, *,
    backend: Optional[Backend] = None,
) -> jax.Array:
    be = resolve_backend(backend)
    return feature_contract_pallas(xi, u, interpret=be.interpret,
                                   split_reduce=be.split_reduce, backend=be)


def feature_matvec(
    xi: jax.Array, t: jax.Array, *,
    backend: Optional[Backend] = None,
) -> jax.Array:
    be = resolve_backend(backend)
    return feature_matvec_pallas(xi, t, interpret=be.interpret, backend=be)


def sinkhorn_halfstep(
    xi: jax.Array,
    t: jax.Array,
    marg: jax.Array,
    *,
    backend: Optional[Backend] = None,
) -> jax.Array:
    be = resolve_backend(backend)
    return sinkhorn_halfstep_pallas(xi, t, marg, interpret=be.interpret,
                                    backend=be)


def log_matvec(
    log_m: jax.Array, t: jax.Array, *,
    backend: Optional[Backend] = None,
) -> jax.Array:
    be = resolve_backend(backend)
    return log_matvec_pallas(log_m, t, interpret=be.interpret, backend=be)


def log_feature_contract(
    log_w: jax.Array, s: jax.Array, *,
    backend: Optional[Backend] = None,
) -> jax.Array:
    be = resolve_backend(backend)
    return log_feature_contract_pallas(
        log_w, s, interpret=be.interpret, split_reduce=be.split_reduce,
        backend=be)


def log_halfstep(
    log_w: jax.Array,
    t: jax.Array,
    lmarg: jax.Array,
    *,
    scale: float = 1.0,
    backend: Optional[Backend] = None,
) -> jax.Array:
    be = resolve_backend(backend)
    return log_halfstep_pallas(log_w, t, lmarg, scale=scale,
                               interpret=be.interpret, backend=be)


# ---------------------------------------------------------------------------
# Fused full iterations
# ---------------------------------------------------------------------------


def fused_sinkhorn_iteration(
    xi: jax.Array,          # (n, r)
    zeta: jax.Array,        # (m, r)
    a: jax.Array,           # (n, B)
    b: jax.Array,           # (m, B)
    u: jax.Array,           # (n, B) current scaling
    *,
    backend: Optional[Backend] = None,
):
    """One full Sinkhorn iteration on the factored kernel, Pallas end to end.

        t   = Xi^T u            (contract)
        v   = b / (Zeta t)      (fused halfstep)
        s   = Zeta^T v          (contract)
        u'  = a / (Xi s)        (fused halfstep)

    Returns (u', v).
    """
    be = resolve_backend(backend)
    t = feature_contract(xi, u, backend=be)
    v = sinkhorn_halfstep(zeta, t, b, backend=be)
    s = feature_contract(zeta, v, backend=be)
    u_new = sinkhorn_halfstep(xi, s, a, backend=be)
    return u_new, v


def _log_contract(log_w, s, be: Backend, axis: Optional[str]):
    """Stage-1 LSE ``LSE_i(log_w + s)`` (r, B). Under a mesh axis the
    kernel reduces this device's rows only; the partial LSEs are one row
    each of an LSE over the devices, so ``psum_logsumexp`` over that row
    combines them (one pmax and one psum of r floats)."""
    t = log_feature_contract(log_w, s, backend=be)
    return t if axis is None else psum_logsumexp(t[None], axis, axis=0)


def fused_log_sinkhorn_iteration(
    log_xi: jax.Array,      # (n, r)
    log_zeta: jax.Array,    # (m, r)
    loga: jax.Array,        # (n, B) masked-log weights
    logb: jax.Array,        # (m, B)
    f: jax.Array,           # (n, B) current potential
    *,
    eps: float,
    backend: Optional[Backend] = None,
    axis: Optional[str] = None,
):
    """One full LOG-domain Sinkhorn iteration, Pallas end to end:

        t  = LSE-contract(logXi, f/eps)                  (r, B)
        g  = eps (log b - LSE(logZeta + t))              (fused log halfstep)
        s  = LSE-contract(logZeta, g/eps)                (r, B)
        f' = eps (log a - LSE(logXi + s))                (fused log halfstep)

    Returns (f', g) — the small-eps twin of :func:`fused_sinkhorn_iteration`.
    ``axis``: the mesh axis the rows are sharded over inside ``shard_map``;
    each contraction's partial LSE is then combined across it.
    """
    be = resolve_backend(backend)
    t = _log_contract(log_xi, f / eps, be, axis)
    g = log_halfstep(log_zeta, t, logb, scale=eps, backend=be)
    s = _log_contract(log_zeta, g / eps, be, axis)
    f_new = log_halfstep(log_xi, s, loga, scale=eps, backend=be)
    return f_new, g


def batched_sinkhorn_halfstep(
    xi: jax.Array,          # (B, n, r) per-problem features of updated side
    u: jax.Array,           # (B, m) other side's current scaling
    marg: jax.Array,        # (B, n) target marginal of the updated side
    zeta: jax.Array,        # (B, m, r) features contracted against u
    *,
    backend: Optional[Backend] = None,
) -> jax.Array:
    """One fused half-step  v_b = marg_b / (Xi_b (Zeta_b^T u_b))  for B
    independent problems (per-problem features, e.g. the BatchedSinkhorn
    engine's bucket groups). Pallas batching adds B as a leading grid axis,
    so the MXU still sees the same (block_n x r) tiles back to back.
    """
    be = resolve_backend(backend)

    def one(xi_b, u_b, marg_b, zeta_b):
        t = feature_contract(zeta_b, u_b[:, None], backend=be)
        return sinkhorn_halfstep(xi_b, t, marg_b[:, None],
                                 backend=be)[:, 0]

    return jax.vmap(one)(xi, u, marg, zeta)


def fused_batched_sinkhorn_iteration(
    xi: jax.Array,          # (B, n, r)
    zeta: jax.Array,        # (B, m, r)
    a: jax.Array,           # (B, n)
    b: jax.Array,           # (B, m)
    u: jax.Array,           # (B, n) current scalings
    *,
    backend: Optional[Backend] = None,
):
    """One full Alg.-1 iteration for B independent problems, Pallas end to
    end:

        t_b  = Xi_b^T u_b ;  v_b = b_b / (Zeta_b t_b)
        s_b  = Zeta_b^T v_b ; u_b' = a_b / (Xi_b s_b)

    Returns (u', v) stacked. Unlike :func:`fused_sinkhorn_iteration` (one
    shared kernel, B marginal columns), every problem here has its own
    feature matrices — the GAN-minibatch shape.

    ``api.BatchedSinkhorn`` reaches the same kernels through its vmapped
    per-problem solver when ``use_pallas`` is on: vmap adds B as a leading
    Pallas grid axis, exactly as here.
    """
    be = resolve_backend(backend)
    v = batched_sinkhorn_halfstep(zeta, u, b, xi, backend=be)
    u_new = batched_sinkhorn_halfstep(xi, v, a, zeta, backend=be)
    return u_new, v


# ---------------------------------------------------------------------------
# Over-relaxation: relax_scaling / relax_log are canonical in
# kernels.fused_loop (imported above, re-exported here) so the megakernel
# module stays import-cycle-free while the XLA solvers in core.sinkhorn
# keep importing them from this namespace.
# ---------------------------------------------------------------------------
# Geometry-chosen dispatch (the pallas_ops() hook consumer)
# ---------------------------------------------------------------------------


def _masked_log(w: jax.Array) -> jax.Array:
    """log w with log(0) pinned to -inf without 0*inf NaN hazards (local
    twin of ``core.geometry._masked_log`` — kernels must not import core)."""
    return jnp.where(w > 0, jnp.log(jnp.where(w > 0, w, 1.0)), -jnp.inf)


PRECISIONS = ("highest", "bf16")


def check_precision(precision: str) -> str:
    """Validate a ``precision=`` execution-policy value (shared with
    ``core.geometry``; kernels must not import core)."""
    if precision not in PRECISIONS:
        raise ValueError(
            f"unknown precision {precision!r}; expected one of {PRECISIONS}"
        )
    return precision


def _store_features(xi, zeta, precision: str):
    """Apply the storage half of the mixed-precision policy: bf16 halves
    the HBM stream of the (n, r)/(m, r) factors — the roofline-dominant
    bytes — while every kernel upcasts tiles to f32 in registers, so the
    contraction/LSE ACCUMULATION precision is unchanged."""
    check_precision(precision)
    if precision == "bf16":
        return xi.astype(jnp.bfloat16), zeta.astype(jnp.bfloat16)
    return xi, zeta


class GeometryOps(NamedTuple):
    """Fused Pallas execution plan for one geometry's cost family.

    ``mode``      — "scaling" (features/scalings) or "log" (log-features/
                    potentials, the small-eps path).
    ``kind``      — the ``pallas_ops()`` spec kind the plan was built from.
    ``features``  — the materialized factors the plan operates on:
                    (xi, zeta) in scaling mode, (log_xi, log_zeta) in log
                    mode; for Gaussian point clouds these come out of the
                    fused feature-map kernel (MXU dot + rank-1 norm
                    corrections + exp — or no exp in log mode — with no
                    (n, r) sq-dist tensor in HBM).
    ``iteration`` — one full fused Alg.-1 iteration:
                    scaling  ``(a, b, u) -> (u', v)``,
                    log      ``(loga, logb, f) -> (f', g)``,
                    marginals/scalings/potentials as (n, B)/(m, B) columns.
    ``make_step`` — ``(a, b, *, momentum, err_reduce) -> (step, init)``
                    where ``step`` is drop-in compatible with
                    ``core.sinkhorn.run_marginal_loop`` and ELEMENTWISE
                    matches ``make_scaling_step`` / ``make_log_step`` over
                    the geometry's XLA operators (same iterates, same
                    marginal error, same masking) — the solver hot loop.
                    ``init`` lifts the primal/dual start values into the
                    loop carry, which tacks on the column marginal of the
                    current iterate (``s = K^T u`` in scaling mode, its
                    log ``log(K^T e^{f/eps})`` (m,) in log mode): the
                    convergence check computes it and the next
                    iteration's column update reuses it, so checking
                    costs no factor pass of its own.
    ``apply_kt``  — scaling mode only: ``u (n,) -> K^T u (m,)`` for the
                    loop-carry initialization.
    ``eps``       — log mode only: the regularization the potentials live
                    at.
    ``make_block_step`` — ``(a, b, *, inner_steps, momentum) ->
                    Optional[(step, init)]``: the PERSISTENT megakernel
                    plan. ``step`` advances ``inner_steps`` full
                    iterations in ONE ``pallas_call`` (``fused_loop``) —
                    factors VMEM-resident, carries on-chip, marginal error
                    emitted at the block boundary only. In scaling mode
                    its carry is ``make_step``'s; in log mode it carries
                    the stage-1 LSE ``t = LSE(logXi + f/eps)`` (r, 1) in
                    place of the column log-marginal, so a solve runs one
                    step or the other, never both. Either way ``(f, g)``
                    (``(u, v)``) and the error match ``make_step``'s
                    elementwise at block boundaries. Returns ``None``
                    when the working set exceeds the VMEM budget
                    (``fused_loop.block_plan_fits``) — callers then fall
                    back to the streaming per-iteration ``make_step``.
    ``interpret`` — whether the plan's kernels run in interpret mode
                    (``backend.interpret`` — kept as a flat field for the
                    solver auto policy and existing call sites).
    ``backend``   — the resolved :class:`Backend` record the plan was
                    built at (budgets, split-k routing, megakernel
                    admission all key off it).
    ``axis``      — the mesh axis the factors' rows are sharded over
                    (log mode under ``shard_map``), else ``None``. Each
                    stage-1 contraction then reduces the local rows only,
                    and its (r, B) partial LSE is combined across the
                    axis before the half-step that reads it; there is no
                    megakernel.
    ``precision`` — the execution policy the plan was built at
                    ("highest" | "bf16"): bf16 stores/streams the factors
                    at half width; all contractions and LSE accumulations
                    stay f32.
    """

    mode: str
    kind: str
    features: Tuple[jax.Array, jax.Array]
    iteration: Callable
    make_step: Callable
    apply_kt: Optional[Callable] = None
    eps: Optional[float] = None
    make_block_step: Optional[Callable] = None
    interpret: bool = False
    precision: str = "highest"
    backend: Optional[Backend] = None
    axis: Optional[str] = None


def _scaling_plan(kind: str, xi, zeta, be: Backend,
                  precision: str = "highest") -> GeometryOps:
    xi, zeta = _store_features(xi, zeta, precision)

    def iteration(a, b, u):
        return fused_sinkhorn_iteration(xi, zeta, a, b, u, backend=be)

    def apply_kt(u):
        t = feature_contract(xi, u[:, None], backend=be)
        return feature_matvec(zeta, t, backend=be)[:, 0]

    def make_step(a, b, *, momentum: float = 1.0,
                  err_reduce: Callable = jnp.sum):
        ac = a[:, None]

        def step(carry):
            u, v, s = carry
            v_new = relax_scaling(b / s, v, momentum)
            t = feature_contract(zeta, v_new[:, None], backend=be)
            if momentum == 1.0:
                # matvec + marginal divide fused in one VMEM pass
                u_new = sinkhorn_halfstep(xi, t, ac, backend=be)[:, 0]
            else:
                kv = feature_matvec(xi, t, backend=be)[:, 0]
                u_new = relax_scaling(a / kv, u, momentum)
            t2 = feature_contract(xi, u_new[:, None], backend=be)
            s_new = feature_matvec(zeta, t2, backend=be)[:, 0]
            err = err_reduce(jnp.abs(v_new * s_new - b))
            return (u_new, v_new, s_new), err

        def init(u0, v0):
            return (u0, v0, apply_kt(u0))

        return step, init

    def make_block_step(a, b, *, inner_steps: int, momentum: float = 1.0):
        n, m = a.shape[0], b.shape[0]
        if not block_plan_fits(n, m, xi.shape[1], 1, xi.dtype, backend=be):
            return None
        ac, bc = a[:, None], b[:, None]

        def step(carry):
            u, v, s = carry
            u2, v2, s2, err = sinkhorn_block_pallas(
                xi, zeta, ac, bc, u[:, None], v[:, None], s[:, None],
                inner_steps=inner_steps, momentum=momentum, backend=be,
            )
            return (u2[:, 0], v2[:, 0], s2[:, 0]), err

        def init(u0, v0):
            return (u0, v0, apply_kt(u0))

        return step, init

    return GeometryOps(mode="scaling", kind=kind, features=(xi, zeta),
                       iteration=iteration, make_step=make_step,
                       apply_kt=apply_kt, make_block_step=make_block_step,
                       interpret=be.interpret, precision=precision,
                       backend=be)


def _log_plan(kind: str, log_xi, log_zeta, eps: float, be: Backend,
              precision: str = "highest",
              axis: Optional[str] = None) -> GeometryOps:
    log_xi, log_zeta = _store_features(log_xi, log_zeta, precision)

    def iteration(loga, logb, f):
        return fused_log_sinkhorn_iteration(
            log_xi, log_zeta, loga, logb, f, eps=eps, backend=be, axis=axis
        )

    def contract_f(f):
        """Stage-1 LSE over logXi, ``t = LSE_i(logXi + f/eps)`` (r, 1)."""
        return _log_contract(log_xi, f[:, None] / eps, be, axis)

    def make_step(a, b, *, momentum: float = 1.0,
                  err_reduce: Callable = jnp.sum):
        loga = _masked_log(a)[:, None]
        logb = _masked_log(b)
        zero = jnp.zeros((b.shape[0], 1), b.dtype)

        def log_col(f):
            """Both LSE stages, ``log(K^T e^{f/eps})`` (m,) — the carried
            column log-marginal: computed once per iteration, it serves
            BOTH the convergence check and the next iteration's g-update
            (the log twin of carrying ``s = K^T u``)."""
            return log_halfstep(log_zeta, contract_f(f), zero, scale=-1.0,
                                backend=be)[:, 0]

        def step(carry):
            f, g, lcol = carry                # lcol = log(K^T e^{f/eps})
            # the half-step kernel's epilogue, scale * (lmarg - lse)
            g_new = relax_log(eps * (logb - lcol), g, momentum)
            t2 = _log_contract(log_zeta, g_new[:, None] / eps, be, axis)
            f_new = relax_log(
                log_halfstep(log_xi, t2, loga, scale=eps,
                             backend=be)[:, 0], f, momentum)
            lcol_new = log_col(f_new)
            err = err_reduce(jnp.abs(jnp.exp(lcol_new + g_new / eps) - b))
            return (f_new, g_new, lcol_new), err

        def init(f0, g0):
            return (f0, g0, log_col(f0))

        return step, init

    def make_block_step(a, b, *, inner_steps: int, momentum: float = 1.0):
        n, m = a.shape[0], b.shape[0]
        # the block iterates on this device's rows with no combine between
        if axis is not None or not block_plan_fits(
                n, m, log_xi.shape[1], 1, log_xi.dtype, backend=be):
            return None
        loga = _masked_log(a)[:, None]
        logb = _masked_log(b)[:, None]
        bc = b[:, None]

        def step(carry):
            f, g, t1 = carry
            f2, g2, t2, err = log_sinkhorn_block_pallas(
                log_xi, log_zeta, loga, logb, bc,
                f[:, None], g[:, None], t1,
                inner_steps=inner_steps, eps=eps, momentum=momentum,
                backend=be,
            )
            return (f2[:, 0], g2[:, 0], t2), err

        def init(f0, g0):
            return (f0, g0, contract_f(f0))

        return step, init

    return GeometryOps(mode="log", kind=kind, features=(log_xi, log_zeta),
                       iteration=iteration, make_step=make_step, eps=eps,
                       make_block_step=make_block_step,
                       interpret=be.interpret, precision=precision,
                       backend=be, axis=axis)


def _paged_scaling_plan(kind: str, xi, zeta, live_x, live_y,
                        page_size: int, be: Backend,
                        precision: str = "highest") -> GeometryOps:
    """Scaling plan over PAGED factor buffers: each contract / half-step
    predicates per page on the live counts (``kernels.paged``), skipping
    the MXU work for all-dead pages. Elementwise equal to
    :func:`_scaling_plan` whenever dead slots carry zero weight/scaling —
    the streaming store's invariant. No megakernel block step yet: paged
    updates run the streaming per-iteration path."""
    xi, zeta = _store_features(xi, zeta, precision)
    kw = dict(page_size=page_size, interpret=be.interpret, backend=be)

    def iteration(a, b, u):
        t = paged_feature_contract_pallas(xi, u, live_x, **kw)
        v = paged_halfstep_pallas(zeta, t, b, live_y, **kw)
        s = paged_feature_contract_pallas(zeta, v, live_y, **kw)
        u_new = paged_halfstep_pallas(xi, s, a, live_x, **kw)
        return u_new, v

    def apply_kt(u):
        t = paged_feature_contract_pallas(xi, u[:, None], live_x, **kw)
        return paged_feature_matvec_pallas(zeta, t, live_y, **kw)[:, 0]

    def make_step(a, b, *, momentum: float = 1.0,
                  err_reduce: Callable = jnp.sum):
        ac = a[:, None]

        def step(carry):
            u, v, s = carry
            # the paged matvec writes ZEROS on all-dead pages, so b / s is
            # 0/0 there — mask to the flat plan's value (b = 0 -> v = 0)
            v_new = relax_scaling(jnp.where(b > 0, b / s, 0.0), v, momentum)
            t = paged_feature_contract_pallas(zeta, v_new[:, None], live_y,
                                              **kw)
            if momentum == 1.0:
                u_new = paged_halfstep_pallas(xi, t, ac, live_x, **kw)[:, 0]
            else:
                kv = paged_feature_matvec_pallas(xi, t, live_x, **kw)[:, 0]
                u_new = relax_scaling(jnp.where(a > 0, a / kv, 0.0), u,
                                      momentum)
            t2 = paged_feature_contract_pallas(xi, u_new[:, None], live_x,
                                               **kw)
            s_new = paged_feature_matvec_pallas(zeta, t2, live_y, **kw)[:, 0]
            err = err_reduce(jnp.abs(v_new * s_new - b))
            return (u_new, v_new, s_new), err

        def init(u0, v0):
            return (u0, v0, apply_kt(u0))

        return step, init

    return GeometryOps(mode="scaling", kind=kind, features=(xi, zeta),
                       iteration=iteration, make_step=make_step,
                       apply_kt=apply_kt, make_block_step=None,
                       interpret=be.interpret, precision=precision,
                       backend=be)


def geometry_ops(geom, *,
                 mode: str = "scaling",
                 precision: str = "highest",
                 backend: Optional[Backend] = None) -> Optional[GeometryOps]:
    """Fused-kernel plan for ``geom``, chosen by the geometry itself.

    ``mode="scaling"`` builds the linear-feature plan (Alg. 1 on scalings);
    ``mode="log"`` builds the log-feature plan (small-eps potentials, exact
    two-stage LSE through the fused log kernels). Returns ``None`` when the
    geometry declares no fused path (dense costs, signed Nystrom factors,
    grids) — callers then fall back to the geometry's XLA operators. The
    spec format is owned by ``Geometry.pallas_ops``; this function only
    maps specs to kernels.

    ``precision="bf16"`` stores/streams the (log-)factors — including the
    feature blocks produced by the fused Gaussian map for point-cloud
    geometries — at half width; contractions and LSE accumulations stay
    f32 (see ``_store_features``).

    ``backend=`` pins the plan to a resolved :class:`Backend` record or
    name (``"interpret"`` is the test configuration); otherwise the
    ambient policy applies. The whole plan — kernel routing (split-k on
    parallel-grid backends), fused-map admissibility, megakernel budget —
    keys off the one record.

    A spec with an ``axis`` (a row-sharded geometry inside ``shard_map``)
    gets the per-shard log plan, which combines each stage-1 LSE across
    the axis; there is no per-shard scaling plan, so scaling mode returns
    ``None`` there.
    """
    if mode not in ("scaling", "log"):
        raise ValueError(f"unknown plan mode {mode!r}")
    check_precision(precision)
    spec = geom.pallas_ops()
    if spec is None:
        return None
    axis = spec.get("axis")
    if axis is not None and mode == "scaling":
        return None
    be = resolve_backend(backend)
    kind = spec["kind"]
    if kind == "factored":
        xi, zeta = spec["xi"], spec["zeta"]
        if mode == "scaling":
            return _scaling_plan(kind, xi, zeta, be, precision)
        return _log_plan(kind, _masked_log(xi), _masked_log(zeta),
                         float(geom.eps), be, precision, axis)
    if kind == "log_factored":
        lxi, lzt = spec["log_xi"], spec["log_zeta"]
        if mode == "log":
            return _log_plan(kind, lxi, lzt, float(spec["eps"]), be,
                             precision, axis)
        return _scaling_plan(kind, jnp.exp(lxi), jnp.exp(lzt), be,
                             precision)
    if kind == "paged":
        if "xi" in spec:
            xi, zeta = spec["xi"], spec["zeta"]
            lxi = lzt = None
        else:
            lxi, lzt = spec["log_xi"], spec["log_zeta"]
            xi, zeta = jnp.exp(lxi), jnp.exp(lzt)
        if mode == "log":
            # dead slots are -inf-pinned potentials — inert in every LSE —
            # so the standard log plan on the flat factors is already
            # exact; there is no paged log fast path (yet)
            if lxi is None:
                lxi, lzt = _masked_log(xi), _masked_log(zeta)
            return _log_plan(kind, lxi, lzt, float(spec["eps"]), be,
                             precision, axis)
        if not paged_supported(be):
            # parallel-grid backends (Triton) cannot lower the paged
            # contract's sequential accumulation — refuse into the flat
            # split-k kernels (still masked-exact), never interpret
            return _scaling_plan(kind, xi, zeta, be, precision)
        return _paged_scaling_plan(
            kind, xi, zeta, spec["page_live_x"], spec["page_live_y"],
            int(spec["page_size"]), be, precision)
    if kind == "gaussian":
        fmap = functools.partial(
            gaussian_feature_map,
            anchors=spec["anchors"], log_const=spec["log_const"],
            inv_eps=spec["inv_eps"], backend=be,
            log_space=(mode == "log"),
        )
        xi, zeta = fmap(spec["x"]), fmap(spec["y"])
        if mode == "scaling":
            return _scaling_plan(kind, xi, zeta, be, precision)
        return _log_plan(kind, xi, zeta, float(geom.eps), be, precision,
                         axis)
    raise ValueError(f"unknown pallas_ops spec kind {kind!r}")
