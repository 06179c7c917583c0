"""Seeded inputs of the benchmark's cells, made on the device.

The point-cloud generators are the benchmark's own copies, so that the data a
cell runs on cannot change with the program. ``key_of`` turns a seed of any
size into a key without dropping its high bits. ``gaussian_q`` gives
Lemma 1's ``q``, from which the drivers scale the anchors,
``u ~ N(0, q eps / 4 I)``.

A cell's problems come from a pool drawn from a fixed key, and the run's
seed re-expresses them (``reexpress``): one random rotation applied to both
clouds and the anchors, and a random order of each cloud's rows. Distances,
and so the kernel, the solution and the work to reach it, stay what they
were, while every number the program reads changes with the seed. So every
seed asks for the same work and runs that differ by seed differ by noise.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["key_of", "lambert_w0", "gaussian_q", "higgs_standin",
           "pointcloud_standin", "reexpress"]


def key_of(seed: int, *tags: int) -> jax.Array:
    """A PRNG key from a non-negative seed of up to 64 bits, folded with tags."""
    seed = int(seed)
    if seed < 0 or seed >= 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF), seed >> 32)
    for t in tags:
        key = jax.random.fold_in(key, t)
    return key


def lambert_w0(z: float) -> float:
    """Principal branch of Lambert's W for z >= 0 (Newton from log1p)."""
    w = math.log1p(z)
    for _ in range(100):
        e = math.exp(w)
        step = (w * e - z) / (e * (w + 1.0))
        w -= step
        if abs(step) < 1e-15 * max(1.0, abs(w)):
            break
    return w


def gaussian_q(R: float, eps: float, d: int) -> float:
    """Lemma 1's q = (R^2 / eps) / (2 d W0(R^2 / (eps d)))."""
    z = R * R / eps / d
    return 0.5 if z == 0.0 else z / (2.0 * lambert_w0(z))


def higgs_standin(key: jax.Array, n: int, d: int):
    """Two anisotropic Gaussians in R^d, n points each (signal, background)."""
    k1, k2, k3 = jax.random.split(key, 3)
    A = 0.5 * jax.random.normal(k3, (d, d)) / jnp.sqrt(d)
    x = jax.random.normal(k1, (n, d)) @ (jnp.eye(d) + A)
    y = jax.random.normal(k2, (n, d)) - 0.5
    return x, y


def _surface(key: jax.Array, n: int) -> jax.Array:
    """n points of one seeded shape in R^3: an ellipsoid's surface or a
    spherical cap, randomly rotated, shifted and jittered, then centred and
    scaled into the unit ball (PointNet's normalisation)."""
    k_kind, k_ax, k_rot, k_pts, k_cap, k_jit = jax.random.split(key, 6)
    v = jax.random.normal(k_pts, (n, 3))
    v = v / jnp.linalg.norm(v, axis=1, keepdims=True)
    axes = jax.random.uniform(k_ax, (3,), minval=0.25, maxval=1.0)
    ellipsoid = v * axes
    centre = jax.random.normal(k_cap, (3,))
    centre = centre / jnp.linalg.norm(centre)
    c = 0.45 * jax.random.normal(k_cap, (n, 3)) + centre
    cap = c / jnp.linalg.norm(c, axis=1, keepdims=True)
    pts = jnp.where(jax.random.bernoulli(k_kind), ellipsoid, cap)
    q, _ = jnp.linalg.qr(jax.random.normal(k_rot, (3, 3)))
    pts = pts @ q + 0.01 * jax.random.normal(k_jit, (n, 3))
    pts = pts - jnp.mean(pts, axis=0)
    return pts / jnp.max(jnp.linalg.norm(pts, axis=1))


def pointcloud_standin(key: jax.Array, n: int):
    """One pair of seeded 3-D shapes of n points each, in the unit ball."""
    kx, ky = jax.random.split(key)
    return _surface(kx, n), _surface(ky, n)


def reexpress(key: jax.Array, x: jax.Array, y: jax.Array, anchors: jax.Array):
    """The same problem in other coordinates: x, y and the anchors rotated by
    one random orthogonal matrix, and the rows of x and of y shuffled."""
    kq, kx, ky = jax.random.split(key, 3)
    d = x.shape[1]
    q, r = jnp.linalg.qr(jax.random.normal(kq, (d, d)))
    q = q * jnp.sign(jnp.diagonal(r))
    rot = functools.partial(jnp.dot, b=q, precision=jax.lax.Precision.HIGHEST)
    x = rot(x)[jax.random.permutation(kx, x.shape[0])]
    y = rot(y)[jax.random.permutation(ky, y.shape[0])]
    return x, y, rot(anchors)
