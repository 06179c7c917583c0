"""Pallas kernel: fused Gaussian positive-feature map (Lemma 1).

Computes  Xi[i, k] = exp( c_k - (2/eps) * ||x_i - u_k||^2 )  without ever
materializing the (n, r) squared-distance matrix in HBM: the MXU produces
the x.u block, the VPU applies the rank-1 norm corrections and the exp, and
only the finished Xi tile is written back.

``log_space=True`` skips the exp in the epilogue and emits ``log Xi``
directly — the small-eps path, where the features themselves would
under/overflow f32 and the log-domain solver consumes ``log Xi`` through
the fused LSE kernels (``logmatvec``). Padded anchors carry
``log_const = -inf`` so their log-features are exactly ``-inf`` (the LSE
identity) and their linear features exactly 0.

Tiling: grid (n/bn, r/br, d/bd). The d axis is the innermost SEQUENTIAL
grid dimension — the x.u partial products accumulate in the f32 output
tile, and the epilogue on the last d-step applies norms (+ exp) in place.
That accumulation is a Mosaic-only idiom: on parallel-grid backends
(Triton) the d axis must ride in ONE block (``d_steps == 1``, enforced by
the tuner's single-block constraint for sequential axes), and point
dimensions too large for that refuse into the XLA feature map at the plan
layer (``backend.fused_map_max_d`` / ``kernels.backend.fused_map_admissible``)
rather than silently interpreting.

Block sizes resolve ``block_* = None`` through ``kernels.autotune``; the
n-cap of 256 that used to be hardcoded here now lives in the tuner's PRIOR
table (working set per step: bn*bd + br*bd + bn*br floats — caps
(256, 512, 512) keep it < 2 MiB, comfortably inside VMEM with double
buffering). Resolution happens OUTSIDE the jitted impl so the chosen
blocks are part of the jit cache key.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import autotune
from .backend import Backend
from .tiling import F32_PRODUCTS, pad_axis

__all__ = ["gaussian_feature_map_kernel", "gaussian_feature_map_pallas"]


def gaussian_feature_map_kernel(
    x_ref, u_ref, x2_ref, u2c_ref, o_ref, *, inv_eps: float, d_steps: int,
    log_space: bool,
):
    """One (bn, br) output tile; accumulates over the d grid axis."""
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # MXU: partial inner products x_blk @ u_blk^T, accumulated in-place.
    o_ref[...] += jax.lax.dot_general(
        x_ref[...],
        u_ref[...],
        (((1,), (1,)), ((), ())),
        precision=F32_PRODUCTS,
        preferred_element_type=jnp.float32,
    )

    @pl.when(k == d_steps - 1)
    def _epilogue():
        dot = o_ref[...]
        # u2c packs  c_k - 2/eps * ||u_k||^2  (precombined in the wrapper);
        # x2 is ||x_i||^2.  log Xi = u2c - 2/eps * x2 + 4/eps * dot.
        log_xi = (
            u2c_ref[...]
            - (2.0 * inv_eps) * x2_ref[...]
            + (4.0 * inv_eps) * dot
        )
        o_ref[...] = log_xi if log_space else jnp.exp(log_xi)


@functools.partial(
    jax.jit,
    static_argnames=(
        "inv_eps", "block_n", "block_r", "block_d", "interpret", "log_space",
    ),
)
def _feature_map_impl(
    x: jax.Array,           # (n, d)
    anchors: jax.Array,     # (r, d)
    log_const: jax.Array,   # (r,)
    *,
    inv_eps: float,
    block_n: int,
    block_r: int,
    block_d: int,
    interpret: bool,
    log_space: bool,
) -> jax.Array:
    n, d = x.shape
    r = anchors.shape[0]
    # pad: zero-rows of x are sliced away; padded anchors get log_const=-inf
    # so their features are exactly 0 (or -inf log-features) and harmless to
    # downstream contractions / LSEs.
    xp = pad_axis(pad_axis(x, 0, block_n), 1, block_d)
    up = pad_axis(pad_axis(anchors, 0, block_r), 1, block_d)
    cp = pad_axis(log_const, 0, block_r, value=-jnp.inf)
    npad, dpad = xp.shape
    rpad = up.shape[0]

    x2 = jnp.sum(xp * xp, axis=-1, keepdims=True)            # (npad, 1)
    u2 = jnp.sum(up * up, axis=-1)                           # (rpad,)
    u2c = (cp - 2.0 * inv_eps * u2)[None, :]                 # (1, rpad)

    grid = (npad // block_n, rpad // block_r, dpad // block_d)
    out = pl.pallas_call(
        functools.partial(
            gaussian_feature_map_kernel, inv_eps=inv_eps, d_steps=grid[2],
            log_space=log_space,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_d), lambda i, j, k: (i, k)),
            pl.BlockSpec((block_r, block_d), lambda i, j, k: (j, k)),
            pl.BlockSpec((block_n, 1), lambda i, j, k: (i, 0)),
            pl.BlockSpec((1, block_r), lambda i, j, k: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_n, block_r), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((npad, rpad), jnp.float32),
        interpret=interpret,
    )(xp, up, x2, u2c)
    return out[:n, :r]


def gaussian_feature_map_pallas(
    x: jax.Array,           # (n, d)
    anchors: jax.Array,     # (r, d)
    log_const: jax.Array,   # (r,) per-anchor offset (incl. -0.5 log r)
    *,
    inv_eps: float,
    block_n: Optional[int] = None,
    block_r: Optional[int] = None,
    block_d: Optional[int] = None,
    interpret: bool = False,
    log_space: bool = False,
    backend: Optional[Backend] = None,
) -> jax.Array:
    n, d = x.shape
    r = anchors.shape[0]
    blocks = autotune.resolve_blocks(
        "feature_map", {"n": n, "r": r, "d": d},
        {"block_n": block_n, "block_r": block_r, "block_d": block_d},
        x.dtype, interpret, backend)
    return _feature_map_impl(
        x, anchors, log_const, inv_eps=inv_eps, interpret=interpret,
        log_space=log_space, **blocks)


def _feature_map_runner(extents, dtype, backend):
    x = autotune._synthetic((extents["n"], extents["d"]), dtype)
    u = autotune._synthetic((extents["r"], extents["d"]), dtype)
    c = autotune._synthetic((extents["r"],), jnp.float32, log=True)

    def run(blocks):
        jax.block_until_ready(
            _feature_map_impl(x, u, c, inv_eps=1.0,
                              interpret=backend.interpret, log_space=False,
                              **blocks))

    return run


autotune.register_runner("feature_map", _feature_map_runner)
