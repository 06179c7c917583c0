"""Shared tile-shape policy for every Pallas kernel in this package.

TPU vector registers are (sublane, lane) = (8, 128) for f32, and Mosaic
lays arrays out in multiples of those — a BlockSpec whose trailing dim is
not a multiple of 128 either fails to lower or silently wastes the lane
dimension. Every kernel therefore pads its operands to lane multiples with
a NEUTRAL value (0 for linear features / scalings, -inf for log-space
entries, 1 for marginals that feed a divide) and slices the result back.

This module is the single owner of that policy:

  * :func:`pad_axis`   — pad one axis up to a multiple with a fill value
  * :func:`pick_block` — block-size selection keyed on the actual extent:
    the smallest lane multiple covering the axis, capped so the working
    set stays inside VMEM. Small problems get small tiles (no 512-wide
    tiles for r=3), large problems get MXU-saturating ones.

Kernels accept ``block_* = None`` and resolve through :func:`pick_block`,
so the (n, m, r, B)-keyed selection happens in exactly one place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["LANE", "SUBLANE", "F32_PRODUCTS", "round_up", "pad_axis",
           "pick_block", "compute_f32"]

LANE = 128      # trailing-dim quantum (f32)
SUBLANE = 8     # second-to-last-dim quantum (f32)

# Precision of every f32 contraction, in the kernels and in the XLA
# operators alike. At the default precision the TPU's MXU rounds both f32
# operands to bf16 (one pass: 3e-3 relative error on a v5e, against 1e-7
# at HIGHEST), which moved converged costs by 3e-5 and kept vmapped XLA
# solves from ever reaching a 1e-6 marginal tolerance.
F32_PRODUCTS = jax.lax.Precision.HIGHEST


def compute_f32(x: jax.Array) -> jax.Array:
    """Upcast a reduced-precision (bf16-stored) feature tile to f32 in
    registers — the compute half of the mixed-precision policy: storage
    and HBM streaming may be bf16, every contraction/LSE ACCUMULATES in
    f32 (Mosaic fuses the widening convert into the consuming op). Shared
    by every kernel in this package so the rule lives in one place."""
    return x.astype(jnp.float32) if x.dtype != jnp.float32 else x


def round_up(size: int, mult: int = LANE) -> int:
    """Smallest multiple of ``mult`` >= ``size``."""
    return ((size + mult - 1) // mult) * mult


def pad_axis(arr: jax.Array, axis: int, mult: int,
             value: float = 0.0) -> jax.Array:
    """Pad ``axis`` of ``arr`` up to a multiple of ``mult`` with ``value``.

    The fill must be NEUTRAL for the kernel consuming the array: 0 for
    linear features/scalings (contributes nothing to a dot), ``-inf`` for
    log entries (identity of logsumexp), 1 for marginals whose divide
    output is sliced away.
    """
    size = arr.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return arr
    widths = [(0, 0)] * arr.ndim
    widths[axis] = (0, pad)
    return jnp.pad(arr, widths, constant_values=value)


def pick_block(size: int, cap: int = 512, mult: int = LANE) -> int:
    """Block size for an axis of extent ``size``: the smallest multiple of
    ``mult`` covering the axis, capped at ``cap`` (itself a multiple of
    ``mult``). With this policy a padded axis always divides evenly by the
    chosen block, so grids never need remainder handling."""
    assert cap % mult == 0, (cap, mult)
    return min(round_up(max(size, 1), mult), cap)
