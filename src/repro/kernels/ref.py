"""Pure-jnp oracles for every Pallas kernel in this package.

Tests sweep shapes/dtypes and assert_allclose(kernel(interpret=True), ref).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .tiling import F32_PRODUCTS

__all__ = [
    "gaussian_feature_map_ref",
    "feature_contract_ref",
    "feature_matvec_ref",
    "sinkhorn_halfstep_ref",
    "log_matvec_ref",
    "log_feature_contract_ref",
    "log_halfstep_ref",
]


def gaussian_feature_map_ref(
    x: jax.Array,          # (n, d)
    anchors: jax.Array,    # (r, d)
    log_const: jax.Array,  # (r,)  per-anchor additive log offset (incl -log r / 2)
    *,
    inv_eps: float,
    log_space: bool = False,
) -> jax.Array:
    """Xi[i,k] = exp(log_const[k] - 2/eps ||x_i - u_k||^2), shape (n, r).

    ``log_space=True`` returns ``log Xi`` (no exp) — the small-eps twin.
    Besides being the test oracle, this is the STREAMING fallback the plan
    layer executes when the fused map refuses to lower (the single-d-block
    constraint on parallel-grid backends; see ``kernels.backend``)."""
    x2 = jnp.sum(x * x, axis=-1)[:, None]
    u2 = jnp.sum(anchors * anchors, axis=-1)[None, :]
    sq = x2 + u2 - 2.0 * jnp.matmul(x, anchors.T, precision=F32_PRODUCTS)
    log_xi = log_const[None, :] - 2.0 * inv_eps * sq
    return log_xi if log_space else jnp.exp(log_xi)


def feature_contract_ref(xi: jax.Array, u: jax.Array) -> jax.Array:
    """t = Xi^T u : (n, r), (n, B) -> (r, B). Phase 1 of a Sinkhorn half-step."""
    return jnp.matmul(xi.T, u, precision=F32_PRODUCTS)


def sinkhorn_halfstep_ref(
    xi: jax.Array,         # (n, r) features of the side being updated
    t: jax.Array,          # (r, B) pre-contracted other side
    marg: jax.Array,       # (n, B) target marginal
) -> jax.Array:
    """out = marg / (Xi @ t) : the fused matvec + marginal divide."""
    return marg / jnp.matmul(xi, t, precision=F32_PRODUCTS)


def feature_matvec_ref(xi: jax.Array, t: jax.Array) -> jax.Array:
    """out = Xi @ t : (n, r), (r, B) -> (n, B). The divide-free twin of
    :func:`sinkhorn_halfstep_ref` (marginal-check matvec)."""
    return jnp.matmul(xi, t, precision=F32_PRODUCTS)


def log_matvec_ref(log_m: jax.Array, t: jax.Array) -> jax.Array:
    """out_j = logsumexp_k(log_m[j, k] + t[k]) : (m, r), (r,) -> (m,)."""
    return jax.scipy.special.logsumexp(log_m + t[None, :], axis=1)


def log_feature_contract_ref(log_w: jax.Array, s: jax.Array) -> jax.Array:
    """t[k, c] = LSE_i(log_w[i, k] + s[i, c]) : (n, r), (n, B) -> (r, B)."""
    return jax.scipy.special.logsumexp(
        log_w[:, :, None] + s[:, None, :], axis=0)


def log_halfstep_ref(log_w: jax.Array, t: jax.Array, lmarg: jax.Array,
                     *, scale: float = 1.0) -> jax.Array:
    """out = scale * (lmarg - LSE_k(log_w[:, k] + t[k, :])), shape (m, B)."""
    lse = jax.scipy.special.logsumexp(
        log_w[:, :, None] + t[None, :, :], axis=1)
    return scale * (lmarg - lse)
