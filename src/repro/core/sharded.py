"""Distributed Sinkhorn via ``shard_map`` — scaling space AND log domain.

The factored kernel is what makes Sinkhorn *distributable*: shard the
SUPPORT of each measure over the ``data`` mesh axis —

    Xi   : (n/p, r) per device        Zeta : (m/p, r) per device
    u,a  : (n/p,)   per device        v,b  : (m/p,)   per device

Each half-iteration is a LOCAL thin contraction followed by ONE tiny
all-reduce of an r-vector:

    t = psum_data( Xi_loc^T u_loc )          # (r,)  <- r floats on the wire
    v_loc = b_loc / (Zeta_loc @ t)

and the log-domain twin is the same traffic: a psum'd logsumexp
(:func:`~repro.distributed.sharding.psum_logsumexp` — ``pmax`` of local
maxima, shifted local sums, ``psum``) produces the replicated r-vector

    t_k = LSE_global_i( logXi[i,k] + f_i/eps )

after which the second LSE stage is purely local. Quadratic Sinkhorn would
instead need every device to see all n columns of K (an O(n m / p)
all-to-all per iteration). The r-vector collective is the entire
communication cost of the paper's method — the term quantified in
EXPERIMENTS.md §Roofline.

Sharding is a first-class execution mode of the Geometry layer:

* :class:`RowShardedGeometry` wraps ANY feature-capable geometry's
  per-device shard. Point-cloud families (Gaussian / arc-cosine) shard
  their raw supports and build local feature rows on device — no global
  feature materialization ever happens.
* :class:`RowShardedFactored` is the explicit-factor special case (kept as
  the stable public name for pre-wrapper callers).
* Both advertise ``spmd_axis``, which makes the UNCHANGED solver core
  (``sinkhorn_geometry`` / ``sinkhorn_log_geometry`` composing
  ``make_scaling_step`` / ``make_log_step`` / ``run_marginal_loop``) psum
  every scalar reduction: the while_loop carries a replicated marginal
  error (all devices exit together — an SPMD requirement) and the dual
  value replicates, which is also what lets ``grad.rot_geometry``'s
  envelope VJP run under ``shard_map`` unchanged.

Uneven supports (``n % p != 0``) are padded up to the next multiple of p
with ZERO-weight atoms whose initial potentials are pinned to ``-inf``
(log) / ``0`` (scaling), so padded atoms contribute exactly nothing to any
psum or LSE from iteration 0 — sharded results match the UNPADDED
single-device solve elementwise, not just at the fixed point.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..distributed.sharding import psum_logsumexp
from .api import _pad_rows
from .geometry import (
    ArcCosinePointCloud,
    FactoredPositive,
    GaussianPointCloud,
    Geometry,
    _compute,
    _matmul,
    _register,
    _stored,
)
from .grad import rot_geometry
from .sinkhorn import (
    SinkhornResult,
    sinkhorn_geometry,
    sinkhorn_log_geometry,
)

__all__ = [
    "RowShardedFactored",
    "RowShardedGeometry",
    "sharded_sinkhorn_factored",
    "sharded_sinkhorn_geometry",
    "sharded_sinkhorn_divergence",
    "make_sharded_sinkhorn",
]

_lse = jax.scipy.special.logsumexp


# ---------------------------------------------------------------------------
# psum'd factored operators (shared by both sharded geometry classes)
# ---------------------------------------------------------------------------


def _psum_factored_ops(xi, zeta, axis: str) -> Tuple[Callable, Callable]:
    """Scaling-space K v / K^T u on local feature rows: one r-vector psum
    per application — the paper's entire per-iteration traffic.
    ``_compute`` upcasts bf16-stored factor rows at application time so
    the local contraction and the psum'd r-vector stay f32."""

    def apply_k(v):                              # (m/p,) -> (n/p,)
        return _matmul(_compute(xi),
                       jax.lax.psum(_matmul(_compute(zeta).T, v), axis))

    def apply_kt(u):                             # (n/p,) -> (m/p,)
        return _matmul(_compute(zeta),
                       jax.lax.psum(_matmul(_compute(xi).T, u), axis))

    return apply_k, apply_kt


def _psum_factored_log_ops(lxi, lzt, eps: float,
                           axis: str) -> Tuple[Callable, Callable]:
    """Log-domain operators: the exact two-stage LSE of
    ``geometry._factored_log_apply`` with the FIRST stage distributed.

    Stage 1 reduces over the sharded support axis, so it runs through the
    psum'd logsumexp (pmax + psum of one r-vector — same wire cost as the
    scaling path); stage 2 reduces over the local r axis only. Positivity
    of the factored kernel keeps the split exact, and -inf log-features of
    zero-weight padded atoms drop out of both stages.
    """

    def log_apply_k(g):                          # log(K e^{g/eps}), (n/p,)
        t = psum_logsumexp(_compute(lzt) + (g / eps)[:, None],
                           axis, axis=0)                             # (r,)
        return _lse(_compute(lxi) + t[None, :], axis=1)

    def log_apply_kt(f):                         # log(K^T e^{f/eps}), (m/p,)
        t = psum_logsumexp(_compute(lxi) + (f / eps)[:, None], axis, axis=0)
        return _lse(_compute(lzt) + t[None, :], axis=1)

    return log_apply_k, log_apply_kt


# ---------------------------------------------------------------------------
# Sharded geometries (used INSIDE shard_map)
# ---------------------------------------------------------------------------


class _PsumOpsMixin:
    """The entire psum'd operator surface, derived from the host class's
    LOCAL ``features()``/``log_features()`` plus its ``axis``/``eps`` —
    one implementation shared by both sharded geometry classes so the
    collective wiring cannot drift between them."""

    @property
    def spmd_axis(self) -> Optional[str]:
        return self.axis

    def operators(self, *, precision: str = "highest"):
        # the mixed-precision policy composes with sharding for free: the
        # LOCAL factor rows store bf16, the psum'd r-vector stays f32
        xi, zeta = (_stored(w, precision) for w in self.features())
        return _psum_factored_ops(xi, zeta, self.axis)

    def log_operators(self, *, precision: str = "highest"):
        lxi, lzt = (_stored(w, precision) for w in self.log_features())
        return _psum_factored_log_ops(lxi, lzt, self.eps, self.axis)

    def apply_k(self, v):
        return self.operators()[0](v)

    def apply_kt(self, u):
        return self.operators()[1](u)

    def log_apply_k(self, g):
        return self.log_operators()[0](g)

    def log_apply_kt(self, f):
        return self.log_operators()[1](f)

    def pallas_ops(self):
        # a fused local plan has no psum in its iteration — every other
        # device's feature rows would be silently dropped. No fused path.
        return None


@dataclasses.dataclass(frozen=True, eq=False)
class RowShardedFactored(_PsumOpsMixin, FactoredPositive):
    """Per-device shard of a factored geometry, used INSIDE ``shard_map``.

    ``xi``/``zeta`` (or ``log_xi``/``log_zeta``) hold the local
    (n/p, r)/(m/p, r) feature rows; the operators produce locally-sharded
    outputs after reducing the shared r-vector over ``axis`` — the only
    cross-device traffic per iteration (a plain psum in scaling space, the
    psum'd logsumexp in log space).
    """

    axis: str = dataclasses.field(default="data",
                                  metadata=dict(static=True))

    def xx(self) -> "RowShardedFactored":
        lxi, _ = self.log_features()
        return RowShardedFactored(log_xi=lxi, log_zeta=lxi, eps=self.eps,
                                  axis=self.axis)

    def yy(self) -> "RowShardedFactored":
        _, lzt = self.log_features()
        return RowShardedFactored(log_xi=lzt, log_zeta=lzt, eps=self.eps,
                                  axis=self.axis)


@dataclasses.dataclass(frozen=True, eq=False)
class RowShardedGeometry(_PsumOpsMixin, Geometry):
    """Per-device shard of ANY feature-capable geometry, INSIDE shard_map.

    ``base`` carries the LOCAL rows of the wrapped family: point-cloud
    geometries (Gaussian, arc-cosine) hold their local support rows (x
    over n, y over m; anchors replicated) and derive local feature rows on
    device, so no global feature matrix is ever materialized; explicit
    factored geometries hold local factor rows. The operators are the
    psum'd thin contraction (scaling) / psum'd two-stage LSE (log), and
    ``spmd_axis`` tells the solver core to psum its scalar reductions.
    """

    base: Geometry
    axis: str = dataclasses.field(default="data",
                                  metadata=dict(static=True))

    @property
    def eps(self) -> float:
        return self.base.eps

    @property
    def shape(self) -> Tuple[int, int]:
        return self.base.shape              # LOCAL (n/p, m/p) shard shape

    @property
    def supports_log(self) -> bool:         # mirrors the wrapped family
        return self.base.supports_log

    @property
    def supports_features(self) -> bool:
        return self.base.supports_features

    def features(self):
        return self.base.features()         # local rows

    def log_features(self):
        return self.base.log_features()

    def cost_matrix(self):
        raise ValueError(
            "RowShardedGeometry has no dense cost view: each device holds "
            "only its local support rows; densify the wrapped geometry "
            "outside shard_map instead"
        )

    def xx(self) -> "RowShardedGeometry":
        return RowShardedGeometry(base=self.base.xx(), axis=self.axis)

    def yy(self) -> "RowShardedGeometry":
        return RowShardedGeometry(base=self.base.yy(), axis=self.axis)


for _cls in (RowShardedFactored, RowShardedGeometry):
    _register(_cls)


# ---------------------------------------------------------------------------
# Host-side plumbing: which fields shard, padding, spec construction
# ---------------------------------------------------------------------------

# Geometry family -> fields whose rows shard over the mesh axis. Every
# other array field (shared anchors, ...) replicates. First-measure fields
# have n rows; second-measure fields m rows.
_ROW_SHARDED_FIELDS = {
    FactoredPositive: ("xi", "zeta", "log_xi", "log_zeta"),
    GaussianPointCloud: ("x", "y"),
    ArcCosinePointCloud: ("x", "y"),
}
_N_FIELDS = ("xi", "log_xi", "x")


def _row_sharded_fields(geom: Geometry) -> Optional[Tuple[str, ...]]:
    for cls in type(geom).__mro__:
        if cls in _ROW_SHARDED_FIELDS:
            return _ROW_SHARDED_FIELDS[cls]
    return None


def _array_fields(geom: Geometry):
    """(name, value) for every non-static, non-None dataclass field — the
    geometry's pytree leaves, in field order."""
    out = []
    for fld in dataclasses.fields(geom):
        if fld.metadata.get("static"):
            continue
        val = getattr(geom, fld.name)
        if val is not None:
            out.append((fld.name, val))
    return out


def _static_kwargs(geom: Geometry) -> dict:
    return {fld.name: getattr(geom, fld.name)
            for fld in dataclasses.fields(geom)
            if fld.metadata.get("static")}


def _auto_mode(geom: Geometry) -> str:
    """Scaling vs log exactly like the local auto table
    (``api._auto_method``): explicit linear-space factors run the scaling
    iteration; every other family — point clouds, log-features — runs the
    small-eps-safe log domain."""
    if isinstance(geom, FactoredPositive) and geom.xi is not None:
        return "scaling"
    return "log"


def _prepare(mesh, geom: Geometry, axis: str):
    """Validate + coerce the geometry into a shardable family.

    Families with a row-sharding rule pass through (point clouds never
    materialize global features); other feature-capable families fall back
    to one global factor materialization.
    """
    if axis not in mesh.axis_names:
        raise ValueError(
            f"mesh has axes {mesh.axis_names}, no axis named {axis!r}"
        )
    if isinstance(geom, RowShardedGeometry):
        geom = geom.base
    if _row_sharded_fields(geom) is None:
        if not geom.supports_features:
            raise ValueError(
                "sharded solve needs a geometry with per-row feature "
                f"structure; {type(geom).__name__} has none (no positive "
                "factors to shard)"
            )
        xi, zeta = geom.features()
        geom = FactoredPositive(xi=xi, zeta=zeta, eps=geom.eps)
    return geom


def _shard_geometry_args(geom: Geometry, axis: str, p: int):
    """Pad the row-sharded fields to multiples of p and build the flat
    (arrays, in_specs, rebuild) triple the shard_map wrapper consumes.

    ``rebuild(*arrays)`` reconstructs the per-device geometry inside the
    body from the local array shards plus the (closed-over) static fields.
    """
    n, m = geom.shape
    n_pad = -(-n // p) * p
    m_pad = -(-m // p) * p
    row_fields = set(_row_sharded_fields(geom))
    names, arrays, specs = [], [], []
    for name, val in _array_fields(geom):
        if name in row_fields:
            target = n_pad if name in _N_FIELDS else m_pad
            val = _pad_rows(val, target, replicate=True)
            specs.append(P(axis, *([None] * (val.ndim - 1))))
        else:
            specs.append(P())                   # replicated (anchors, ...)
        names.append(name)
        arrays.append(val)
    cls = type(geom)
    statics = _static_kwargs(geom)

    def rebuild(*arrs) -> Geometry:
        return cls(**dict(zip(names, arrs)), **statics)

    return arrays, tuple(specs), rebuild, (n, m, n_pad, m_pad)


def _result_specs(axis: str) -> SinkhornResult:
    """Supports and potentials shard over ``axis``; the scalars (psum'd
    cost/error, loop counter) replicate."""
    return SinkhornResult(
        u=P(axis), v=P(axis), f=P(axis), g=P(axis),
        cost=P(), n_iter=P(), marginal_err=P(), converged=P(),
    )


# ---------------------------------------------------------------------------
# The SPMD bodies (run per device inside shard_map)
# ---------------------------------------------------------------------------


def _sharded_body(geom_local: Geometry, a, b, w1, w2, *, axis, mode,
                  tol, max_iter, momentum, check_every=1,
                  precision="highest") -> SinkhornResult:
    """Runs INSIDE shard_map. All arrays are per-device shards.

    Composes the SAME solver entry points as the single-device path —
    ``sinkhorn_geometry`` / ``sinkhorn_log_geometry`` with their
    ``make_scaling_step`` / ``make_log_step`` / ``run_marginal_loop``
    building blocks unchanged. The only distribution-aware pieces are the
    geometry's psum'd operators and the psum'd scalar reductions selected
    through ``geom.spmd_axis`` — masking, warm starts and momentum are
    byte-for-byte the single-device semantics.
    """
    if geom_local.spmd_axis is None:
        geom_local = RowShardedGeometry(base=geom_local, axis=axis)
    if mode == "log":
        return sinkhorn_log_geometry(
            geom_local, a, b, tol=tol, max_iter=max_iter, momentum=momentum,
            f_init=w1, g_init=w2, use_pallas=False,
            check_every=check_every, precision=precision,
        )
    return sinkhorn_geometry(
        geom_local, a, b, tol=tol, max_iter=max_iter, momentum=momentum,
        u_init=w1, use_pallas=False, check_every=check_every,
        precision=precision,
    )


def _divergence_body(geom_local: Geometry, a, b, *, axis, tol,
                     max_iter) -> jax.Array:
    """Sinkhorn divergence (Eq. 2) per device: three psum'd envelope
    solves through the UNCHANGED ``rot_geometry`` custom VJP — the psum'd
    dual value is already replicated, so the scalar (and its gradients,
    via psum's transpose) come out correct without divergence-specific
    distribution code."""
    g = RowShardedGeometry(base=geom_local, axis=axis)
    w_xy = rot_geometry(g, a, b, tol, max_iter)
    w_xx = rot_geometry(g.xx(), a, a, tol, max_iter)
    w_yy = rot_geometry(g.yy(), b, b, tol, max_iter)
    return w_xy - 0.5 * (w_xx + w_yy)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------


def sharded_sinkhorn_geometry(
    mesh, geom: Geometry, a, b, *, axis: str = "data", mode: str = "auto",
    tol: float = 1e-6, max_iter: int = 2000, momentum: float = 1.0,
    f_init: Optional[jax.Array] = None, g_init: Optional[jax.Array] = None,
    inner_steps: Optional[int] = None, check_every: Optional[int] = None,
    precision: str = "highest",
) -> SinkhornResult:
    """Shard-map solve of any feature-capable Geometry on ``mesh``.

    Inputs are globally shaped; supports shard over ``axis`` (padded to a
    multiple of the axis size with inert zero-weight atoms when
    ``n % p != 0``); the feature dimension r and the scalar results
    replicate. ``mode`` picks the iteration space: ``"scaling"`` (plain
    psum'd contractions), ``"log"`` (psum'd-LSE operators, mandatory at
    small eps where scalings over/underflow), or ``"auto"`` (the local
    auto table's choice: scaling for explicit linear factors, log for
    everything else). ``f_init``/``g_init`` warm-start the potentials
    (eps-annealing across sharded stages) and ``momentum`` applies the
    usual over-relaxation — semantics identical to the single-device
    solvers, whose step builders run unchanged inside the SPMD body.
    """
    if mode not in ("auto", "scaling", "log"):
        raise ValueError(
            f"mode must be 'auto' | 'scaling' | 'log', got {mode!r}"
        )
    if inner_steps is not None and int(inner_steps) > 1:
        raise ValueError(
            "inner_steps > 1 (the persistent megakernel) is not available "
            "on sharded solves: the fused block iterates on LOCAL feature "
            "rows only and would silently drop the per-iteration psum. "
            "Use check_every= for the fewer-syncs cadence win, or solve on "
            "one device for the megakernel."
        )
    check_every = 1 if check_every is None else int(check_every)
    geom = _prepare(mesh, geom, axis)
    if mode == "auto":
        mode = _auto_mode(geom)
    if mode == "log" and not geom.supports_log:
        raise ValueError(
            f"{type(geom).__name__} has no log-domain operators; use "
            "mode='scaling'"
        )
    p = mesh.shape[axis]
    arrays, geom_specs, rebuild, (n, m, n_pad, m_pad) = \
        _shard_geometry_args(geom, axis, p)
    dtype = a.dtype
    eps = geom.eps

    a_p = _pad_rows(a, n_pad, replicate=False)
    b_p = _pad_rows(b, m_pad, replicate=False)
    if mode == "log":
        # padded atoms start at -inf (and a = 0 forces the same through
        # the solver's masked _log_init) so they contribute exp(-inf) = 0
        # to every LSE from iteration 0 — sharded iterates match the
        # UNPADDED single-device solve elementwise, not just at the fixed
        # point
        w1 = jnp.zeros((n,), dtype) if f_init is None else f_init
        w2 = jnp.zeros((m,), dtype) if g_init is None else g_init
        w1 = _pad_rows(w1, n_pad, replicate=False, fill=-jnp.inf)
        w2 = _pad_rows(w2, m_pad, replicate=False, fill=-jnp.inf)
    else:
        # scaling space warm-starts u only (g_init is unused, exactly like
        # the single-device scaling runner): the first half-step rebuilds
        # v = b / K^T u from scratch. Zero scalings keep padded atoms inert.
        u0 = jnp.ones((n,), dtype) if f_init is None \
            else jnp.exp(f_init / eps)
        w1 = _pad_rows(u0, n_pad, replicate=False)
        w2 = _pad_rows(jnp.ones((m,), dtype), m_pad, replicate=False)

    def body(*args):
        geom_local = rebuild(*args[:len(arrays)])
        la, lb, lw1, lw2 = args[len(arrays):]
        return _sharded_body(
            geom_local, la, lb, lw1, lw2, axis=axis, mode=mode, tol=tol,
            max_iter=max_iter, momentum=momentum, check_every=check_every,
            precision=precision,
        )

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=geom_specs + (P(axis), P(axis), P(axis), P(axis)),
        out_specs=_result_specs(axis),
        check_vma=False,
    )
    res = fn(*arrays, a_p, b_p, w1, w2)
    if n_pad == n and m_pad == m:
        return res
    return res._replace(u=res.u[:n], v=res.v[:m],
                        f=res.f[:n], g=res.g[:m])


def sharded_sinkhorn_divergence(
    mesh, geom: Geometry, a: Optional[jax.Array] = None,
    b: Optional[jax.Array] = None, *, axis: str = "data",
    tol: float = 1e-6, max_iter: int = 2000,
) -> jax.Array:
    """Sharded Sinkhorn divergence: three psum'd log-domain envelope
    solves inside ONE shard_map. Differentiable in the geometry's arrays
    (supports, features, shared anchors) through ``rot_geometry``'s
    envelope VJP, which runs under shard_map unchanged — the psum'd dual
    value is replicated and psum's transpose routes every shard's
    contribution into the leaf cotangents."""
    geom = _prepare(mesh, geom, axis)
    if not geom.supports_log:
        raise ValueError(
            f"{type(geom).__name__} has no log-domain operators; the "
            "sharded divergence runs in log space"
        )
    n, m = geom.shape
    a = jnp.full((n,), 1.0 / n) if a is None else a
    b = jnp.full((m,), 1.0 / m) if b is None else b
    p = mesh.shape[axis]
    arrays, geom_specs, rebuild, (n, m, n_pad, m_pad) = \
        _shard_geometry_args(geom, axis, p)
    a_p = _pad_rows(a, n_pad, replicate=False)
    b_p = _pad_rows(b, m_pad, replicate=False)

    def body(*args):
        geom_local = rebuild(*args[:len(arrays)])
        la, lb = args[len(arrays):]
        return _divergence_body(geom_local, la, lb, axis=axis, tol=tol,
                                max_iter=max_iter)

    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=geom_specs + (P(axis), P(axis)),
        out_specs=P(),
        check_vma=False,
    )
    return fn(*arrays, a_p, b_p)


def sharded_sinkhorn_factored(
    mesh, xi, zeta, a, b, *, eps: float, axis: str = "data",
    mode: str = "scaling", tol: float = 1e-6, max_iter: int = 2000,
    momentum: float = 1.0, f_init: Optional[jax.Array] = None,
    g_init: Optional[jax.Array] = None,
) -> SinkhornResult:
    """Sharded solve on explicit positive factors K = xi @ zeta.T."""
    return sharded_sinkhorn_geometry(
        mesh, FactoredPositive(xi=xi, zeta=zeta, eps=eps), a, b,
        axis=axis, mode=mode, tol=tol, max_iter=max_iter, momentum=momentum,
        f_init=f_init, g_init=g_init,
    )


def make_sharded_sinkhorn(mesh, *, axis: str = "data", eps: float,
                          mode: str = "scaling", tol: float = 1e-6,
                          max_iter: int = 2000):
    """Build a solver ``fn(xi, zeta, a, b)`` bound to ``mesh``.

    Inputs are globally-shaped; supports shard over ``axis``; the feature
    dimension r and the result replicate.
    """

    def fn(xi, zeta, a, b) -> SinkhornResult:
        return sharded_sinkhorn_factored(
            mesh, xi, zeta, a, b, eps=eps, axis=axis, mode=mode, tol=tol,
            max_iter=max_iter,
        )

    return fn
