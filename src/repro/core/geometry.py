"""The Geometry layer: one kernel-operator protocol for every cost family.

The paper's central observation is that the *representation of the Gibbs
kernel* — dense matrix, exact positive-feature factorization ``K = Xi
Zeta^T`` (Lemmas 1/3), signed Nystrom low-rank (Altschuler et al. '18), or
a separable grid convolution — determines both the cost of a Sinkhorn
matvec and whether the iteration converges at all. A :class:`Geometry`
packages that choice behind one small operator protocol so every solver,
autodiff rule and Pallas dispatch in the repo is generic in the kernel:

    ``apply_k`` / ``apply_kt``          scaling-space matvecs  K v, K^T u
    ``log_apply_k`` / ``log_apply_kt``  log-domain operators
                                        log(K e^{g/eps}), log(K^T e^{f/eps})
    ``cost_matrix()``                   dense cost for the quadratic baselines
    ``dense_kernel()``                  the exact dense K the operators apply
    ``rebuild_at(eps)``                 re-derive the kernel at a new eps
                                        (``anneal_capable`` families only)
    ``features()`` / ``log_features()`` materialized positive factors
    ``xx()`` / ``yy()``                 the symmetric sub-geometries the
                                        Sinkhorn divergence needs
    ``pallas_ops()``                    hook consumed by ``kernels.ops``
                                        to pick fused TPU kernels

Cost families shipped here:

* :class:`DenseCost`          — explicit (n, m) cost, O(nm) matvecs; the
                                paper's ``Sin`` baseline and the universal
                                fallback every other family can densify to.
* :class:`FactoredPositive`   — explicit positive features (or
                                log-features): exact ``K = Xi Zeta^T``,
                                O(r(n+m)) matvecs, converges for any r.
* :class:`GaussianPointCloud` — Lemma-1 features rebuilt from (x, y,
                                anchors) at ANY eps: the one annealing- and
                                learnable-anchor-capable family.
* :class:`ArcCosinePointCloud`— Lemma-3 perturbed arc-cosine features
                                (relu-family kernels with a kappa > 0
                                positivity floor).
* :class:`NystromLowRank`     — the paper's ``Nys`` baseline: signed
                                low-rank factors; same O(l(n+m)) matvec
                                cost but no log-domain operators and a
                                documented small-eps divergence mode.
* :class:`GridSeparable`      — separable costs on regular grids: the
                                Gibbs kernel is a Kronecker product, so a
                                matvec is d axis-wise convolutions at
                                O(n^{1+1/d}) — the images/histograms
                                workload (convolutional Wasserstein).

Every class is a frozen dataclass registered as a JAX pytree (arrays are
leaves; eps and other scalars are static metadata), so geometries flow
through ``jit`` / ``vmap`` / ``grad`` and the envelope-theorem VJPs in
``grad.py`` can differentiate *through a geometry's parameters*.
"""
from __future__ import annotations

import abc
import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.ops import check_precision
from ..kernels.tiling import F32_PRODUCTS, compute_f32
from .features import (
    arccos_features,
    gaussian_log_features,
    gaussian_q,
)
from .features import _anchor_log_const  # noqa: F401  (pallas_ops hook)

__all__ = [
    "Geometry",
    "DenseCost",
    "FactoredPositive",
    "GaussianPointCloud",
    "ArcCosinePointCloud",
    "NystromLowRank",
    "GridSeparable",
    "as_geometry",
    "squared_euclidean",
    "gibbs_kernel",
    "neglog_kernel_cost",
    "data_radius",
]

_lse = jax.scipy.special.logsumexp


# ---------------------------------------------------------------------------
# Free functions (pre-protocol public API, still the shared primitives)
# ---------------------------------------------------------------------------


def squared_euclidean(x: jax.Array, y: jax.Array) -> jax.Array:
    """C_ij = ||x_i - y_j||^2, shapes (n,d),(m,d) -> (n,m)."""
    x2 = jnp.sum(x * x, axis=-1)[:, None]
    y2 = jnp.sum(y * y, axis=-1)[None, :]
    C = x2 + y2 - 2.0 * _matmul(x, y.T)
    return jnp.maximum(C, 0.0)


def gibbs_kernel(C: jax.Array, eps: float) -> jax.Array:
    """K = exp(-C / eps)."""
    return jnp.exp(-C / eps)


def neglog_kernel_cost(k_matrix: jax.Array, eps: float) -> jax.Array:
    """c(x,y) = -eps log k(x,y) — the kernel-first cost of Eq. (7)."""
    return -eps * jnp.log(k_matrix)


def data_radius(*point_sets: jax.Array) -> jax.Array:
    """R = max_i ||p_i||_2 over all supplied supports (for Lemma 1's q)."""
    return jnp.max(
        jnp.stack([jnp.max(jnp.linalg.norm(p, axis=-1)) for p in point_sets])
    )


def _masked_log(w: jax.Array) -> jax.Array:
    """log w with log(0) pinned to -inf without 0*inf NaN hazards."""
    return jnp.where(w > 0, jnp.log(jnp.where(w > 0, w, 1.0)), -jnp.inf)


def _stored(arr: jax.Array, precision: str) -> jax.Array:
    """Apply the storage half of the mixed-precision execution policy.

    ``precision="bf16"`` keeps the loop-invariant kernel representation
    (features, log-features, dense Gibbs kernel, low-rank factors) in
    bfloat16 — halving the HBM bytes the roofline says the iteration is
    bound by — while every contraction/LSE still ACCUMULATES in f32 (the
    bf16 operand promotes on use; on TPU the widening convert fuses into
    the matmul, so only the streamed bytes change)."""
    check_precision(precision)
    return arr.astype(jnp.bfloat16) if precision == "bf16" else arr


def _compute(arr: jax.Array) -> jax.Array:
    """Upcast a bf16-STORED operand to f32 at application time.

    Placed INSIDE the operator closures so the hoisted array keeps bf16
    storage (and bf16 HBM streaming — XLA/Mosaic fuse the widening
    convert into the consuming contraction) while the multiply/accumulate
    runs in f32. Relying on dtype promotion instead is a trap: JAX's weak
    types demote ``weak-f32 @ bf16`` to a bf16 contraction, silently
    dropping the accumulation precision the policy guarantees. Thin alias
    of :func:`repro.kernels.tiling.compute_f32` — the kernels' register
    upcast — so the rule has one implementation."""
    return compute_f32(arr)


def _matmul(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` with f32 products (:data:`~repro.kernels.tiling.
    F32_PRODUCTS`): a vmapped matvec (``solve_many``, the service's
    runners) lowers to the MXU, which would otherwise round the scalings
    to bf16."""
    return jnp.matmul(a, b, precision=F32_PRODUCTS)


def _factored_log_apply(log_u: jax.Array, log_w: jax.Array,
                        s: jax.Array) -> jax.Array:
    """log( (e^{log_u} e^{log_w}^T) e^{s} ) via the exact two-stage LSE.

    Positivity of the factored kernel makes the split exact:
        out_i = LSE_k( log_u[i,k] + LSE_j( log_w[j,k] + s_j ) ).
    Cost O(r (n + m)) — the paper's linear-time matvec, in log space.
    """
    t = _lse(log_w + s[:, None], axis=0)          # (r,)
    return _lse(log_u + t[None, :], axis=1)


def _shifted_log_product(log_u: jax.Array, log_w: jax.Array) -> jax.Array:
    """log(e^{log_u} @ e^{log_w}^T) densely, max-shifted per row so peak
    memory stays O(nm) instead of the O(nmr) broadcast of a pairwise LSE."""
    m1 = jnp.max(log_u, axis=1, keepdims=True)                 # (n, 1)
    m2 = jnp.max(log_w, axis=1, keepdims=True)                 # (m, 1)
    K = jnp.exp(log_u - m1) @ jnp.exp(log_w - m2).T
    return _masked_log(K) + m1 + m2.T


# ---------------------------------------------------------------------------
# Protocol
# ---------------------------------------------------------------------------


class Geometry(abc.ABC):
    """One entropic-OT cost family: the kernel-operator protocol.

    Subclasses carry their own parametrization (cost matrix, features,
    point clouds + anchors, low-rank factors, grid axes) plus ``eps``, and
    expose the operators above. Capability flags:

    ``anneal_capable`` — ``rebuild_at(eps)`` re-derives the kernel at an
        arbitrary eps (geometry-parameterized families). Families whose
        kernel is pinned to the eps their factors were drawn at raise.
    ``supports_log`` — log-domain operators exist (requires an entrywise
        POSITIVE kernel; signed Nystrom factors do not qualify).
    ``supports_features`` — ``features()`` can materialize strictly
        positive factors (what ``method='sharded'`` and the fused Pallas
        iteration consume).
    """

    anneal_capable: bool = False
    supports_log: bool = True
    supports_features: bool = False

    # -- shape ---------------------------------------------------------------

    @property
    @abc.abstractmethod
    def shape(self) -> Tuple[int, int]:
        """(n, m): support sizes of the two measures."""

    # -- scaling-space operators ---------------------------------------------

    @abc.abstractmethod
    def apply_k(self, v: jax.Array) -> jax.Array:
        """K v, shape (m,) -> (n,)."""

    @abc.abstractmethod
    def apply_kt(self, u: jax.Array) -> jax.Array:
        """K^T u, shape (n,) -> (m,)."""

    def operators(self, *, precision: str = "highest"
                  ) -> Tuple[Callable, Callable]:
        """(matvec, rmatvec) with loop-invariant work HOISTED.

        Solvers call this once before entering their ``lax.while_loop`` so
        per-family precomputation (materializing exp(-C/eps), exponentiating
        log-features, building per-axis grid kernels) happens once per
        solve, not twice per iteration — XLA does not hoist such work out
        of a while_loop body. Defaults to the bound per-call operators.

        ``precision`` is the mixed-precision execution policy (see
        :func:`_stored`): ``"bf16"`` stores the hoisted kernel
        representation at half width with f32 accumulation. Families
        override to apply it; this default validates and ignores it (no
        hoisted representation to store).
        """
        check_precision(precision)
        return self.apply_k, self.apply_kt

    # -- log-domain operators ------------------------------------------------

    def log_apply_k(self, g: jax.Array) -> jax.Array:
        """log(K e^{g/eps}), shape (m,) -> (n,)."""
        raise ValueError(
            f"{type(self).__name__} has no log-domain operators "
            "(kernel entries are not guaranteed positive); use a "
            "scaling-space method"
        )

    def log_apply_kt(self, f: jax.Array) -> jax.Array:
        """log(K^T e^{f/eps}), shape (n,) -> (m,)."""
        raise ValueError(
            f"{type(self).__name__} has no log-domain operators "
            "(kernel entries are not guaranteed positive); use a "
            "scaling-space method"
        )

    def log_operators(self, *, precision: str = "highest"
                      ) -> Tuple[Callable, Callable]:
        """(log_matvec, log_rmatvec) with loop-invariant work hoisted —
        the log-domain twin of :meth:`operators` (``precision="bf16"``
        stores log-features/log-kernels at half width; every LSE still
        accumulates in f32)."""
        check_precision(precision)
        return self.log_apply_k, self.log_apply_kt

    # -- dense views ---------------------------------------------------------

    @abc.abstractmethod
    def cost_matrix(self) -> jax.Array:
        """Dense (n, m) ground cost for the quadratic baselines.

        Point-cloud families return the TRUE squared-Euclidean cost (the
        paper's ``Sin`` baseline); factored families return the induced
        cost ``-eps log(Xi Zeta^T)`` so all methods share one fixed point.
        """

    def dense_kernel(self) -> jax.Array:
        """The exact dense (n, m) kernel the operators apply — the oracle
        every operator is property-tested against."""
        return jnp.exp(self.log_dense_kernel())

    def log_dense_kernel(self) -> jax.Array:
        """log of :meth:`dense_kernel` (positive-kernel families)."""
        raise ValueError(
            f"{type(self).__name__} kernel may be signed; use dense_kernel()"
        )

    # -- eps handling --------------------------------------------------------

    def rebuild_at(self, eps: float) -> "Geometry":
        """This geometry's kernel re-derived at ``eps`` (annealing)."""
        if float(eps) == float(self.eps):
            return self
        raise ValueError(
            f"{type(self).__name__} pins the kernel to the eps its factors "
            f"were built at ({self.eps}); got {eps}. Build the problem from "
            "point clouds (GaussianPointCloud) to enable eps-annealing."
        )

    # -- factored views ------------------------------------------------------

    def features(self) -> Tuple[jax.Array, jax.Array]:
        """(xi, zeta): strictly positive factors with K = xi @ zeta.T."""
        raise ValueError(
            "no factored kernel available "
            f"({type(self).__name__}); use a quadratic method"
        )

    def log_features(self) -> Tuple[jax.Array, jax.Array]:
        """(log_xi, log_zeta) — log of :meth:`features`."""
        xi, zeta = self.features()
        return _masked_log(xi), _masked_log(zeta)

    # -- divergence sub-geometries -------------------------------------------

    def xx(self) -> "Geometry":
        """The (mu, mu) self-geometry — W(mu, mu) term of the divergence."""
        raise ValueError(
            f"{type(self).__name__} does not define self-geometries; the "
            "Sinkhorn divergence needs a per-measure parametrization"
        )

    def yy(self) -> "Geometry":
        """The (nu, nu) self-geometry — W(nu, nu) term of the divergence."""
        raise ValueError(
            f"{type(self).__name__} does not define self-geometries; the "
            "Sinkhorn divergence needs a per-measure parametrization"
        )

    # -- distribution hook ---------------------------------------------------

    @property
    def spmd_axis(self) -> Optional[str]:
        """Mesh axis this geometry's operators psum over, or ``None``.

        Single-device geometries return ``None``. The row-sharded wrappers
        in ``core.sharded`` return their mesh axis, which tells the solver
        core (``sinkhorn.py``) and the envelope VJP (``grad.py``) to psum
        every scalar reduction (marginal error, dual value, correlation
        term) so while_loop carries and results replicate across devices.
        """
        return None

    # -- accelerator dispatch ------------------------------------------------

    def pallas_ops(self) -> Optional[dict]:
        """Spec consumed by ``kernels.ops.geometry_ops`` to choose fused
        Pallas kernels (fused feature map, feature_contract, batched
        half-step). ``None`` means no fused path — callers fall back to the
        XLA operators above."""
        return None


class _FeatureKernelOps:
    """Mixin: the factored-kernel operators, derived entirely from
    ``features()`` / ``log_features()``. Shared by every positive-feature
    family so the O(r(n+m)) matvec and exact two-stage-LSE plumbing exists
    in exactly one place. ``operators()``/``log_operators()`` materialize
    the factors ONCE and close over them, so solver while_loops never
    recompute features per iteration."""

    def operators(self, *, precision: str = "highest"):
        xi, zeta = (_stored(w, precision) for w in self.features())
        return (lambda v: _matmul(_compute(xi), _matmul(_compute(zeta).T, v)),
                lambda u: _matmul(_compute(zeta), _matmul(_compute(xi).T, u)))

    def log_operators(self, *, precision: str = "highest"):
        eps = self.eps
        lxi, lzt = (_stored(w, precision) for w in self.log_features())
        return (lambda g: _factored_log_apply(_compute(lxi), _compute(lzt),
                                              g / eps),
                lambda f: _factored_log_apply(_compute(lzt), _compute(lxi),
                                              f / eps))

    def apply_k(self, v):
        return self.operators()[0](v)

    def apply_kt(self, u):
        return self.operators()[1](u)

    def log_apply_k(self, g):
        return self.log_operators()[0](g)

    def log_apply_kt(self, f):
        return self.log_operators()[1](f)

    def log_dense_kernel(self):
        lxi, lzt = self.log_features()
        return _shifted_log_product(lxi, lzt)


# ---------------------------------------------------------------------------
# Dense cost
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class DenseCost(Geometry):
    """Explicit (n, m) ground cost; Gibbs kernel K = exp(-C/eps).

    O(nm) matvecs — the universal fallback and the paper's ``Sin``
    baseline. Anneal-capable: the kernel is re-derivable at any eps.
    """

    C: jax.Array
    eps: float = dataclasses.field(metadata=dict(static=True))

    anneal_capable = True
    supports_log = True

    @property
    def shape(self) -> Tuple[int, int]:
        return self.C.shape

    def operators(self, *, precision: str = "highest"):
        # materialized ONCE per solve (bf16 storage under the policy)
        K = _stored(jnp.exp(-self.C / self.eps), precision)
        return (lambda v: _matmul(_compute(K), v),
                lambda u: _matmul(_compute(K).T, u))

    def log_operators(self, *, precision: str = "highest"):
        eps = self.eps
        negC = _stored(-self.C / eps, precision)
        return (lambda g: _lse(_compute(negC) + (g / eps)[None, :], axis=1),
                lambda f: _lse(_compute(negC) + (f / eps)[:, None], axis=0))

    def apply_k(self, v):
        return self.operators()[0](v)

    def apply_kt(self, u):
        return self.operators()[1](u)

    def log_apply_k(self, g):
        return self.log_operators()[0](g)

    def log_apply_kt(self, f):
        return self.log_operators()[1](f)

    def cost_matrix(self):
        return self.C

    def log_dense_kernel(self):
        return -self.C / self.eps

    def rebuild_at(self, eps: float) -> "DenseCost":
        return self if float(eps) == float(self.eps) else \
            DenseCost(self.C, float(eps))


# ---------------------------------------------------------------------------
# Exact positive-feature factorization (Lemma 1 / Lemma 3 output form)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class FactoredPositive(_FeatureKernelOps, Geometry):
    """K = Xi Zeta^T from explicit positive features or log-features.

    The paper's central object: every matvec costs O(r(n+m)) and — all
    entries being strictly positive — Sinkhorn converges for ANY r. The
    kernel is pinned to the eps the features were drawn at, so this family
    is not anneal-capable; use :class:`GaussianPointCloud` for annealing.
    """

    xi: Optional[jax.Array] = None
    zeta: Optional[jax.Array] = None
    log_xi: Optional[jax.Array] = None
    log_zeta: Optional[jax.Array] = None
    eps: float = dataclasses.field(kw_only=True,
                                   metadata=dict(static=True))

    anneal_capable = False
    supports_log = True
    supports_features = True

    def __post_init__(self):
        have_lin = self.xi is not None and self.zeta is not None
        have_log = self.log_xi is not None and self.log_zeta is not None
        if have_lin == have_log:
            raise ValueError(
                "FactoredPositive needs exactly one factor pair: "
                "(xi, zeta) or (log_xi, log_zeta)"
            )

    @property
    def shape(self) -> Tuple[int, int]:
        if self.xi is not None:
            return self.xi.shape[0], self.zeta.shape[0]
        return self.log_xi.shape[0], self.log_zeta.shape[0]

    @property
    def rank(self) -> int:
        return (self.xi if self.xi is not None else self.log_xi).shape[1]

    def features(self):
        if self.xi is not None:
            return self.xi, self.zeta
        return jnp.exp(self.log_xi), jnp.exp(self.log_zeta)

    def log_features(self):
        if self.log_xi is not None:
            return self.log_xi, self.log_zeta
        return _masked_log(self.xi), _masked_log(self.zeta)

    def cost_matrix(self):
        return -self.eps * self.log_dense_kernel()

    def xx(self) -> "FactoredPositive":
        if self.xi is not None:
            return FactoredPositive(xi=self.xi, zeta=self.xi, eps=self.eps)
        return FactoredPositive(log_xi=self.log_xi, log_zeta=self.log_xi,
                                eps=self.eps)

    def yy(self) -> "FactoredPositive":
        if self.zeta is not None:
            return FactoredPositive(xi=self.zeta, zeta=self.zeta,
                                    eps=self.eps)
        return FactoredPositive(log_xi=self.log_zeta, log_zeta=self.log_zeta,
                                eps=self.eps)

    def pallas_ops(self):
        if self.xi is not None:
            return {"kind": "factored", "xi": self.xi, "zeta": self.zeta}
        # log mode: hand the raw log-factors over so the log plan never
        # round-trips through exp (small-eps safety); the scaling plan
        # exponentiates once at plan-build time.
        return {"kind": "log_factored", "log_xi": self.log_xi,
                "log_zeta": self.log_zeta, "eps": self.eps}


# ---------------------------------------------------------------------------
# Lemma 1: Gaussian point clouds (anchors + eps-rebuildable)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class GaussianPointCloud(_FeatureKernelOps, Geometry):
    """Point clouds + Lemma-1 anchors: features re-derived at any eps.

    The only family that composes with an ``EpsSchedule`` (annealing) and
    exposes learnable-anchor gradients (the GAN theta of Eq. 18).
    ``cost_matrix`` is the TRUE squared-Euclidean cost — the ``Sin``
    baseline — while the operators apply the Lemma-1 Monte-Carlo kernel.
    """

    x: jax.Array                        # (n, d)
    y: jax.Array                        # (m, d)
    anchors: jax.Array                  # (r, d)
    eps: float = dataclasses.field(metadata=dict(static=True))
    R: float = dataclasses.field(metadata=dict(static=True))

    anneal_capable = True
    supports_log = True
    supports_features = True

    @classmethod
    def build(cls, x, y, anchors, *, eps: float,
              R: Optional[float] = None) -> "GaussianPointCloud":
        R = float(data_radius(x, y)) if R is None else float(R)
        return cls(x=x, y=y, anchors=anchors, eps=float(eps), R=R)

    @property
    def shape(self) -> Tuple[int, int]:
        return self.x.shape[0], self.y.shape[0]

    @property
    def q(self) -> float:
        return gaussian_q(self.R, self.eps, self.x.shape[-1])

    def log_features(self):
        q = self.q
        lxi = gaussian_log_features(self.x, self.anchors, eps=self.eps, q=q)
        lzt = gaussian_log_features(self.y, self.anchors, eps=self.eps, q=q)
        return lxi, lzt

    def features(self):
        lxi, lzt = self.log_features()
        return jnp.exp(lxi), jnp.exp(lzt)

    def cost_matrix(self):
        return squared_euclidean(self.x, self.y)

    def rebuild_at(self, eps: float) -> "GaussianPointCloud":
        return self if float(eps) == float(self.eps) else \
            GaussianPointCloud(self.x, self.y, self.anchors,
                               eps=float(eps), R=self.R)

    def xx(self) -> "GaussianPointCloud":
        return GaussianPointCloud(self.x, self.x, self.anchors,
                                  eps=self.eps, R=self.R)

    def yy(self) -> "GaussianPointCloud":
        return GaussianPointCloud(self.y, self.y, self.anchors,
                                  eps=self.eps, R=self.R)

    def pallas_ops(self):
        r = self.anchors.shape[0]
        log_const = (_anchor_log_const(self.anchors, self.q, self.eps)
                     - 0.5 * jnp.log(jnp.asarray(r, jnp.float32)))
        return {
            "kind": "gaussian",
            "x": self.x,
            "y": self.y,
            "anchors": self.anchors,
            "log_const": log_const,
            "inv_eps": 1.0 / self.eps,
        }


# ---------------------------------------------------------------------------
# Lemma 3: perturbed arc-cosine point clouds
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class ArcCosinePointCloud(_FeatureKernelOps, Geometry):
    """Lemma-3 perturbed arc-cosine kernel k_s(x, y) + kappa on point clouds.

    Features are relu-rectified random projections plus one constant
    sqrt(kappa) coordinate, so the kernel is bounded below by kappa > 0
    even though individual features may be zero (the log-features carry
    -inf entries, which the exact two-stage LSE handles).

    The induced cost is c = -eps log(k_s + kappa); its Gibbs kernel at eps
    is k_s + kappa for EVERY eps, i.e. the kernel is eps-invariant —
    annealing is a no-op for this family, hence not anneal-capable.
    """

    x: jax.Array                        # (n, d)
    y: jax.Array                        # (m, d)
    anchors: jax.Array                  # (r, d), u ~ N(0, sigma^2 I)
    eps: float = dataclasses.field(metadata=dict(static=True))
    s: int = dataclasses.field(default=1, metadata=dict(static=True))
    sigma: float = dataclasses.field(default=1.5, metadata=dict(static=True))
    kappa: float = dataclasses.field(default=1e-3, metadata=dict(static=True))

    anneal_capable = False
    supports_log = True
    supports_features = True

    def __post_init__(self):
        if not self.kappa > 0:
            raise ValueError(
                "ArcCosinePointCloud needs kappa > 0 (Lemma 3's positivity "
                f"floor), got {self.kappa}"
            )

    @property
    def shape(self) -> Tuple[int, int]:
        return self.x.shape[0], self.y.shape[0]

    def features(self):
        kw = dict(s=self.s, sigma=self.sigma, kappa=self.kappa)
        return (arccos_features(self.x, self.anchors, **kw),
                arccos_features(self.y, self.anchors, **kw))

    def cost_matrix(self):
        return -self.eps * self.log_dense_kernel()

    def xx(self) -> "ArcCosinePointCloud":
        return dataclasses.replace(self, y=self.x)

    def yy(self) -> "ArcCosinePointCloud":
        return dataclasses.replace(self, x=self.y)

    def pallas_ops(self):
        xi, zeta = self.features()
        return {"kind": "factored", "xi": xi, "zeta": zeta}


# ---------------------------------------------------------------------------
# Nystrom signed low-rank (the paper's Nys baseline)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class NystromLowRank(Geometry):
    """K_tilde = L @ Rt: landmark-Nystrom factors of the Gibbs kernel.

    Same O(l(n+m)) matvec cost as the positive-feature path, BUT entries
    of K_tilde can be NEGATIVE: Sinkhorn scalings can cross zero and the
    iteration diverges at small eps (paper Figs. 1/3/5). There is no
    log-domain operator (LSE needs positive entries) and no well-defined
    induced cost; divergence is surfaced through
    ``SinkhornResult.diverged`` rather than raw NaNs.
    """

    L: jax.Array                        # (n, l)
    Rt: jax.Array                       # (l, m)
    eps: float = dataclasses.field(metadata=dict(static=True))

    anneal_capable = False
    supports_log = False
    supports_features = False

    @classmethod
    def from_point_clouds(
        cls, x: jax.Array, y: jax.Array, *, eps: float, rank: int,
        key: jax.Array, ridge: float = 1e-10,
    ) -> "NystromLowRank":
        """Landmark-Nystrom factorization of exp(-||x-y||^2/eps).

        Uniform landmark sampling + eigenvalue-truncated pseudo-inverse
        (stable in f32): invert only the spectrum above tau * lambda_max.
        """
        pool = jnp.concatenate([x, y], axis=0)
        idx = jax.random.choice(key, pool.shape[0], (rank,), replace=False)
        z = pool[idx]                                       # (l, d) landmarks
        K_xz = jnp.exp(-squared_euclidean(x, z) / eps)      # (n, l)
        K_zy = jnp.exp(-squared_euclidean(z, y) / eps)      # (l, m)
        K_zz = jnp.exp(-squared_euclidean(z, z) / eps)
        w, Q = jnp.linalg.eigh(K_zz)
        tau = ridge if ridge > 1e-8 else 1e-5
        keep = w > tau * jnp.max(w)
        w_inv = jnp.where(keep, 1.0 / jnp.where(keep, w, 1.0), 0.0)
        inv = (Q * w_inv[None, :]) @ Q.T
        return cls(L=K_xz @ inv, Rt=K_zy, eps=float(eps))

    @property
    def shape(self) -> Tuple[int, int]:
        return self.L.shape[0], self.Rt.shape[1]

    @property
    def rank(self) -> int:
        return self.L.shape[1]

    def operators(self, *, precision: str = "highest"):
        L, Rt = _stored(self.L, precision), _stored(self.Rt, precision)
        return (lambda v: _matmul(_compute(L), _matmul(_compute(Rt), v)),
                lambda u: _matmul(_compute(Rt).T, _matmul(_compute(L).T, u)))

    def apply_k(self, v):
        return self.L @ (self.Rt @ v)

    def apply_kt(self, u):
        return self.Rt.T @ (self.L.T @ u)

    def dense_kernel(self):
        return self.L @ self.Rt

    def cost_matrix(self):
        raise ValueError(
            "the signed Nystrom kernel has no well-defined induced cost "
            "(-eps log K_tilde hits negative entries); build a DenseCost "
            "from the true ground cost instead"
        )


# ---------------------------------------------------------------------------
# Separable costs on regular grids (images / histograms workload)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True, eq=False)
class GridSeparable(Geometry):
    """Separable cost on a regular grid: C = sum_k c_k(i_k, j_k).

    The Gibbs kernel is then a Kronecker product K = K_1 x ... x K_d, so a
    matvec is d axis-wise convolutions — O(n^{1+1/d}) for n grid points
    instead of O(n^2) (convolutional Wasserstein; Solomon et al. '15).
    Per-axis costs are squared distances of the axis coordinates, so the
    total cost is the squared Euclidean distance between grid points.

    ``axes_x`` / ``axes_y`` are per-dimension coordinate vectors; measures
    live on the cartesian products in C (row-major) order, i.e. a weight
    vector is ``image.reshape(-1)``. Anneal-capable: the tiny per-axis
    kernels rebuild at any eps.
    """

    axes_x: Tuple[jax.Array, ...]       # d arrays, lengths (n_1, ..., n_d)
    axes_y: Tuple[jax.Array, ...]       # d arrays, lengths (m_1, ..., m_d)
    eps: float = dataclasses.field(metadata=dict(static=True))

    anneal_capable = True
    supports_log = True
    supports_features = False

    @classmethod
    def build(cls, axes_x, axes_y=None, *, eps: float) -> "GridSeparable":
        axes_x = tuple(jnp.asarray(t) for t in axes_x)
        axes_y = axes_x if axes_y is None else \
            tuple(jnp.asarray(t) for t in axes_y)
        return cls(axes_x=axes_x, axes_y=axes_y, eps=float(eps))

    def __post_init__(self):
        if len(self.axes_x) != len(self.axes_y) or not self.axes_x:
            raise ValueError(
                "GridSeparable needs matching, non-empty per-dimension axis "
                f"tuples; got {len(self.axes_x)} x and {len(self.axes_y)} y"
            )

    @property
    def ndim(self) -> int:
        return len(self.axes_x)

    @property
    def grid_shape_x(self) -> Tuple[int, ...]:
        return tuple(t.shape[0] for t in self.axes_x)

    @property
    def grid_shape_y(self) -> Tuple[int, ...]:
        return tuple(t.shape[0] for t in self.axes_y)

    @property
    def shape(self) -> Tuple[int, int]:
        n = m = 1
        for t in self.axes_x:
            n *= t.shape[0]
        for t in self.axes_y:
            m *= t.shape[0]
        return n, m

    def _axis_costs(self):
        """Per-axis (n_k, m_k) squared-distance costs."""
        return tuple(
            (tx[:, None] - ty[None, :]) ** 2
            for tx, ty in zip(self.axes_x, self.axes_y)
        )

    @staticmethod
    def _conv(mats, grid, v):
        """d axis-wise contractions: one small (n_k, m_k) matmul per axis."""
        V = v.reshape(grid)
        for k, Mk in enumerate(mats):
            V = jnp.moveaxis(jnp.tensordot(
                Mk, V, axes=(1, k), precision=F32_PRODUCTS), 0, k)
        return V.reshape(-1)

    @staticmethod
    def _log_conv(log_mats, grid, s):
        """Sequential axis-wise LSE: exact because every K_k is positive."""
        out = s.reshape(grid)
        for k, logK in enumerate(log_mats):
            t = jnp.moveaxis(out, k, -1)                    # (..., in_k)
            t = _lse(logK[..., :, :] + t[..., None, :], axis=-1)
            out = jnp.moveaxis(t, -1, k)                    # (..., out_k)
        return out.reshape(-1)

    def operators(self, *, precision: str = "highest"):
        # per-axis kernels are tiny ((n_k, m_k), streamed once per
        # contraction) — bf16 storage is applied for policy uniformity,
        # not for a measurable byte win
        Ks = tuple(_stored(jnp.exp(-ck / self.eps), precision)  # built ONCE
                   for ck in self._axis_costs())
        KTs = tuple(Kk.T for Kk in Ks)
        gy, gx = self.grid_shape_y, self.grid_shape_x
        return (lambda v: self._conv([_compute(k) for k in Ks], gy, v),
                lambda u: self._conv([_compute(k) for k in KTs], gx, u))

    def log_operators(self, *, precision: str = "highest"):
        eps = self.eps
        logKs = tuple(_stored(-ck / eps, precision)
                      for ck in self._axis_costs())
        logKTs = tuple(lk.T for lk in logKs)
        gy, gx = self.grid_shape_y, self.grid_shape_x
        return (lambda g: self._log_conv([_compute(k) for k in logKs],
                                         gy, g / eps),
                lambda f: self._log_conv([_compute(k) for k in logKTs],
                                         gx, f / eps))

    def apply_k(self, v):
        return self.operators()[0](v)

    def apply_kt(self, u):
        return self.operators()[1](u)

    def log_apply_k(self, g):
        return self.log_operators()[0](g)

    def log_apply_kt(self, f):
        return self.log_operators()[1](f)

    def cost_matrix(self):
        C = None
        for ck in self._axis_costs():
            if C is None:
                C = ck
            else:
                n0, m0 = C.shape
                nk, mk = ck.shape
                C = (C[:, None, :, None] + ck[None, :, None, :]) \
                    .reshape(n0 * nk, m0 * mk)
        return C

    def log_dense_kernel(self):
        return -self.cost_matrix() / self.eps

    def rebuild_at(self, eps: float) -> "GridSeparable":
        return self if float(eps) == float(self.eps) else \
            GridSeparable(self.axes_x, self.axes_y, eps=float(eps))

    def xx(self) -> "GridSeparable":
        return GridSeparable(self.axes_x, self.axes_x, eps=self.eps)

    def yy(self) -> "GridSeparable":
        return GridSeparable(self.axes_y, self.axes_y, eps=self.eps)


# ---------------------------------------------------------------------------
# Pytree registration + coercion helper
# ---------------------------------------------------------------------------


def _register(cls):
    fields = dataclasses.fields(cls)
    data = [f.name for f in fields if not f.metadata.get("static")]
    meta = [f.name for f in fields if f.metadata.get("static")]
    jax.tree_util.register_dataclass(cls, data_fields=data, meta_fields=meta)
    return cls


for _cls in (DenseCost, FactoredPositive, GaussianPointCloud,
             ArcCosinePointCloud, NystromLowRank, GridSeparable):
    _register(_cls)


def as_geometry(obj, *, eps: Optional[float] = None) -> Geometry:
    """Coerce ``obj`` into a Geometry: pass-through for geometries, a dense
    (n, m) cost array becomes :class:`DenseCost` (requires ``eps``)."""
    if isinstance(obj, Geometry):
        return obj if eps is None else obj.rebuild_at(eps)
    arr = jnp.asarray(obj)
    if arr.ndim == 2:
        if eps is None:
            raise ValueError("as_geometry(cost_array) requires eps=")
        return DenseCost(arr, float(eps))
    raise TypeError(f"cannot interpret {type(obj).__name__} as a Geometry")
