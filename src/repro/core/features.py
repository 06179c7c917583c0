"""Positive feature maps (the paper's central object).

A *positive feature map* phi : X -> (R*_+)^r defines a kernel
``k(x, y) = <phi(x), phi(y)> > 0`` and therefore a cost
``c(x, y) = -eps * log k(x, y)`` whose Gibbs kernel factorizes EXACTLY:

    K = exp(-C / eps) = Xi @ Zeta.T,   Xi = phi(X) in R_+^{n x r}.

Every Sinkhorn matvec then costs O(r (n + m)) instead of O(n m), and —
because all entries are strictly positive — Sinkhorn converges for ANY r,
unlike signed low-rank approximations (Nystrom).

This module implements:
  * Lemma 1  — positive random features for the Gaussian kernel
               exp(-||x-y||^2 / eps)  (unbiased, ratio-bounded).
  * Lemma 3  — perturbed arc-cosine features k_s(x,y) + kappa.
  * learned  — Lemma-1 features with *learnable anchors* (the paper's GAN
               construction: phi_theta with theta the anchor locations).

All maps are computed in log-space first (numerically safe for small eps)
and exponentiated at the end; log-features feed the log-domain solver
directly.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..kernels.tiling import F32_PRODUCTS

__all__ = [
    "lambert_w0",
    "gaussian_q",
    "GaussianFeatureMap",
    "ArcCosineFeatureMap",
    "init_gaussian_features",
    "gaussian_log_features",
    "gaussian_features",
    "arccos_features",
]


def lambert_w0(z: float, iters: int = 64) -> float:
    """Principal branch W0 of the Lambert function for z >= 0.

    Solves w * exp(w) = z with Halley's method. Config-time scalar math
    (numpy, not traced) — used to pick the variance q of Lemma 1.
    """
    if z < 0:
        raise ValueError("lambert_w0 defined here for z >= 0 only")
    if z == 0.0:
        return 0.0
    # Classic initial guess: log-based for large z, series for small.
    w = math.log1p(z) if z < math.e else math.log(z) - math.log(math.log(z))
    for _ in range(iters):
        ew = math.exp(w)
        f = w * ew - z
        # Halley step.
        denom = ew * (w + 1.0) - (w + 2.0) * f / (2.0 * w + 2.0)
        w_next = w - f / denom
        if abs(w_next - w) < 1e-15 * (1.0 + abs(w_next)):
            w = w_next
            break
        w = w_next
    return w


def gaussian_q(R: float, eps: float, d: int) -> float:
    """The paper's q = (R^2/eps) / (2 d W0(R^2 / (eps d))) (Lemma 1).

    q balances the variance of the anchor distribution rho = N(0, q*eps/4 I)
    against the amplitude bound psi = 2 (2q)^{d/2} of Assumption 1.
    """
    z = (R * R / eps) / d
    if z == 0.0:
        return 0.5  # limit: W0(z) ~ z, q -> 1/(2) * (z/(W0 z)) -> 1/2
    return z / (2.0 * lambert_w0(z))


# ---------------------------------------------------------------------------
# Lemma 1: Gaussian kernel exp(-||x - y||^2 / eps)
# ---------------------------------------------------------------------------
#
#   phi(x, u) = (2q)^{d/4} exp(-2 eps^-1 ||x - u||^2) exp(eps^-1 ||u||^2 / q)
#   u ~ rho = N(0, (q * eps / 4) I_d)
#   E_rho[phi(x,u) phi(y,u)] = exp(-||x-y||^2/eps)          (exact, unbiased)
#
# The per-anchor constant  c_k = (d/4) log(2q) + eps^-1 ||u_k||^2 / q  folds
# into a single additive log-offset, so
#
#   log phi(x, u_k) = c_k - 2 eps^-1 ||x - u_k||^2
#
# and the Monte-Carlo feature matrix (including the 1/sqrt(r) weight) is
#
#   log Xi[i, k] = c_k - (1/2) log r - 2 eps^-1 ||x_i - u_k||^2 .
#
# ||x - u||^2 expands to ||x||^2 + ||u||^2 - 2 x.u  — one MXU matmul plus
# rank-1 terms; this is what the Pallas kernel fuses with the exp.


@dataclasses.dataclass(frozen=True)
class GaussianFeatureMap:
    """Static config for Lemma-1 features."""

    r: int                 # number of random anchors
    d: int                 # ambient dimension
    eps: float             # entropic regularization (the kernel temperature)
    R: float               # data radius bound: x in B(0, R)

    @property
    def q(self) -> float:
        return gaussian_q(self.R, self.eps, self.d)

    @property
    def sigma2(self) -> float:
        # anchor distribution variance: q * eps / 4
        return self.q * self.eps / 4.0

    @property
    def psi(self) -> float:
        # Assumption-1 amplitude bound: 2 (2q)^{d/2}
        return 2.0 * (2.0 * self.q) ** (self.d / 2.0)

    def init(self, key: jax.Array) -> jax.Array:
        """Sample anchors U ~ N(0, sigma2 I), shape (r, d)."""
        return jnp.sqrt(self.sigma2) * jax.random.normal(
            key, (self.r, self.d), dtype=jnp.float32
        )


def init_gaussian_features(key: jax.Array, fmap: GaussianFeatureMap) -> jax.Array:
    return fmap.init(key)


def _anchor_log_const(anchors: jax.Array, q: float, eps: float) -> jax.Array:
    """c_k = (d/4) log(2q) + eps^-1 ||u_k||^2 / q, shape (r,)."""
    d = anchors.shape[-1]
    u2 = jnp.sum(anchors * anchors, axis=-1)
    return 0.25 * d * jnp.log(2.0 * q) + u2 / (q * eps)


def gaussian_log_features(
    x: jax.Array,
    anchors: jax.Array,
    *,
    eps: float,
    q: float,
    include_sqrt_r: bool = True,
) -> jax.Array:
    """log Xi, shape (n, r): log of the Lemma-1 Monte-Carlo feature matrix.

    x: (n, d) points; anchors: (r, d). Differentiable in both (the GAN path
    learns the anchors). Computed via the matmul expansion of ||x - u||^2 so
    the inner contraction hits the MXU on TPU.
    """
    x = jnp.asarray(x)
    anchors = jnp.asarray(anchors)
    r = anchors.shape[0]
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)            # (n, 1)
    u2 = jnp.sum(anchors * anchors, axis=-1)[None, :]       # (1, r)
    xu = jnp.matmul(x, anchors.T, precision=F32_PRODUCTS)  # (n, r)  MXU
    sqdist = x2 + u2 - 2.0 * xu
    logphi = _anchor_log_const(anchors, q, eps)[None, :] - 2.0 / eps * sqdist
    if include_sqrt_r:
        logphi = logphi - 0.5 * jnp.log(jnp.asarray(r, dtype=logphi.dtype))
    return logphi


def gaussian_features(
    x: jax.Array, anchors: jax.Array, *, eps: float, q: float
) -> jax.Array:
    """Xi = exp(log Xi): strictly positive feature matrix, shape (n, r)."""
    return jnp.exp(gaussian_log_features(x, anchors, eps=eps, q=q))


# ---------------------------------------------------------------------------
# Lemma 3: perturbed arc-cosine kernel k_s(x, y) + kappa
# ---------------------------------------------------------------------------
#
#   phi_ac(x, u) = sigma^{d/2} sqrt(2) max(0, u.x)^s exp(-||u||^2/4 (1 - 1/sigma^2))
#   u ~ N(0, sigma^2 I),  plus one constant coordinate sqrt(kappa).
#
# Output dim r + 1 (the kappa coordinate is shared). kappa > 0 guarantees
# k >= kappa > 0 even though individual relu features may be zero.


@dataclasses.dataclass(frozen=True)
class ArcCosineFeatureMap:
    r: int
    d: int
    s: int = 1              # rectification order (0: step, 1: relu, 2: sq-relu)
    sigma: float = 1.5      # importance-sampling widening (> 1)
    kappa: float = 1e-3     # positivity floor

    def init(self, key: jax.Array) -> jax.Array:
        return self.sigma * jax.random.normal(key, (self.r, self.d), jnp.float32)


def arccos_features(
    x: jax.Array, anchors: jax.Array, *, s: int, sigma: float, kappa: float
) -> jax.Array:
    """Arc-cosine positive features, shape (n, r + 1).

    k_theta(x, y) = (1/r) sum_k ac_k(x) ac_k(y) + kappa  ->  k_s(x, y) + kappa.
    """
    n = x.shape[0]
    r, d = anchors.shape
    proj = jnp.matmul(x, anchors.T, precision=F32_PRODUCTS)  # (n, r)
    rect = jnp.maximum(proj, 0.0) ** s if s > 0 else (proj > 0).astype(x.dtype)
    u2 = jnp.sum(anchors * anchors, axis=-1)[None, :]
    damp = jnp.exp(-0.25 * u2 * (1.0 - 1.0 / (sigma * sigma)))
    amp = sigma ** (d / 2.0) * jnp.sqrt(2.0)
    feats = amp * rect * damp / jnp.sqrt(float(r))
    const = jnp.full((n, 1), jnp.sqrt(kappa), dtype=feats.dtype)
    return jnp.concatenate([feats, const], axis=-1)
