"""Pallas kernels for the factored-kernel Sinkhorn half-step.

One half-step  v <- b / (Zeta (Xi^T u))  splits into:

  phase 1  feature_contract : t = Xi^T u        (r, B) — reduction over n
  phase 2  sinkhorn_halfstep: v = b / (Zeta t)  (m, B) — matvec + divide FUSED

Fusing the marginal divide into phase 2 saves an HBM round-trip of the
(m, B) product — on a v5e at 819 GB/s that round-trip is the dominant cost
of the whole iteration once r is small (the op is memory-bound; see
EXPERIMENTS.md §Perf napkin math).

``feature_matvec_pallas`` is phase 2 WITHOUT the divide — the solver's
convergence check needs the raw column marginal ``K^T u`` once per
iteration, and it reuses the same tiling.

The batch dim B (independent Sinkhorn problems — GAN minibatch pairs) rides
whole in both kernels; the MXU sees (bn x r) @ (r x B) tiles. All trailing
dims (r, B) are padded to lane multiples via ``kernels.tiling`` with
neutral fills (0 for features/scalings, 1 for marginals feeding a divide)
and sliced back.

Backends: phase 2 is one parallel grid axis over rows — it lowers on both
Mosaic (TPU) and Triton (GPU) unchanged. Phase 1 accumulates across the n
grid axis into a revisited output block, which is a sequential-grid idiom
only Mosaic supports; ``split_reduce=True`` selects the split-k variant
(each grid cell writes its own partial slot, XLA sums the slots) that
parallel-grid backends can lower. Block sizes resolve ``block_* = None``
through ``kernels.autotune`` (static ``pick_block`` prior, measured winner
when tuning is enabled); resolution happens OUTSIDE the jitted impls so
the chosen blocks are part of the jit cache key.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import autotune
from .backend import Backend
from .tiling import F32_PRODUCTS, LANE, compute_f32 as _f32, pad_axis

__all__ = [
    "feature_contract_pallas",
    "sinkhorn_halfstep_pallas",
    "feature_matvec_pallas",
]


def _feature_contract_kernel(xi_ref, u_ref, t_ref):
    """t += Xi_blk^T u_blk; n is the innermost (sequential) grid axis."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        t_ref[...] = jnp.zeros_like(t_ref)

    t_ref[...] += jax.lax.dot_general(
        _f32(xi_ref[...]),
        u_ref[...],
        (((0,), (0,)), ((), ())),          # contract the n axis
        precision=F32_PRODUCTS,
        preferred_element_type=jnp.float32,
    )


def _feature_contract_splitk_kernel(xi_ref, u_ref, t_ref):
    """Split-k twin: grid cell (i, j) writes its OWN (1, br, B) partial —
    no cross-program accumulation, so the kernel lowers on parallel-grid
    backends (Triton CTAs) where revisiting an output block is a race."""
    t_ref[...] = jax.lax.dot_general(
        _f32(xi_ref[...]),
        u_ref[...],
        (((0,), (0,)), ((), ())),
        precision=F32_PRODUCTS,
        preferred_element_type=jnp.float32,
    )[None]


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_r", "interpret")
)
def _feature_contract_impl(
    xi: jax.Array,          # (n, r)
    u: jax.Array,           # (n, B)
    *,
    block_n: int,
    block_r: int,
    interpret: bool,
) -> jax.Array:
    n, r = xi.shape
    B = u.shape[1]
    xp = pad_axis(pad_axis(xi, 0, block_n), 1, block_r)
    up = pad_axis(pad_axis(u, 0, block_n), 1, LANE)
    Bp = up.shape[1]
    grid = (xp.shape[1] // block_r, xp.shape[0] // block_n)
    t = pl.pallas_call(
        _feature_contract_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_r), lambda i, j: (j, i)),
            pl.BlockSpec((block_n, Bp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, Bp), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[1], Bp), jnp.float32),
        interpret=interpret,
    )(xp, up)
    return t[:r, :B]


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_r", "interpret")
)
def _feature_contract_splitk_impl(
    xi: jax.Array,
    u: jax.Array,
    *,
    block_n: int,
    block_r: int,
    interpret: bool,
) -> jax.Array:
    n, r = xi.shape
    B = u.shape[1]
    xp = pad_axis(pad_axis(xi, 0, block_n), 1, block_r)
    up = pad_axis(pad_axis(u, 0, block_n), 1, LANE)
    Bp = up.shape[1]
    n_steps = xp.shape[0] // block_n
    grid = (xp.shape[1] // block_r, n_steps)
    partials = pl.pallas_call(
        _feature_contract_splitk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_r), lambda i, j: (j, i)),
            pl.BlockSpec((block_n, Bp), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_r, Bp), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_steps, xp.shape[1], Bp),
                                       jnp.float32),
        interpret=interpret,
    )(xp, up)
    # the k-combine runs in XLA: one (n_steps, r, B) sum, race-free
    return jnp.sum(partials, axis=0)[:r, :B]


def feature_contract_pallas(
    xi: jax.Array,          # (n, r)
    u: jax.Array,           # (n, B)
    *,
    block_n: Optional[int] = None,
    block_r: Optional[int] = None,
    interpret: bool = False,
    split_reduce: bool = False,
    backend: Optional[Backend] = None,
) -> jax.Array:
    """t = Xi^T u, shape (r, B). Zero-padded rows/columns contribute 0."""
    n, r = xi.shape
    blocks = autotune.resolve_blocks(
        "feature_contract", {"n": n, "r": r, "B": u.shape[1]},
        {"block_n": block_n, "block_r": block_r}, xi.dtype, interpret,
        backend)
    impl = _feature_contract_splitk_impl if split_reduce \
        else _feature_contract_impl
    return impl(xi, u, interpret=interpret, **blocks)


def _halfstep_kernel(xi_ref, t_ref, marg_ref, o_ref):
    """o = marg / (Xi_blk @ t) — matvec + divide in one VMEM pass."""
    kv = jax.lax.dot_general(
        _f32(xi_ref[...]),
        t_ref[...],
        (((1,), (0,)), ((), ())),
        precision=F32_PRODUCTS,
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = marg_ref[...] / kv


def _matvec_kernel(xi_ref, t_ref, o_ref):
    """o = Xi_blk @ t — the divide-free twin (convergence-check marginal)."""
    o_ref[...] = jax.lax.dot_general(
        _f32(xi_ref[...]),
        t_ref[...],
        (((1,), (0,)), ((), ())),
        precision=F32_PRODUCTS,
        preferred_element_type=jnp.float32,
    )


def _matvec_like_call(kernel, xi, t, extra, *, block_n, interpret):
    """Shared tiling for the (n, r) @ (r, B) kernels: r rides whole (lane
    padded), n blocks, B lane padded; returns the (n, B) slice. One
    parallel grid axis over row blocks — lowers on Mosaic AND Triton."""
    n, r = xi.shape
    B = t.shape[1]
    xp = pad_axis(pad_axis(xi, 0, block_n), 1, LANE)
    tp = pad_axis(pad_axis(t, 0, LANE), 1, LANE)
    rp, Bp = tp.shape
    operands = [xp, tp]
    in_specs = [
        pl.BlockSpec((block_n, rp), lambda i: (i, 0)),
        pl.BlockSpec((rp, Bp), lambda i: (0, 0)),
    ]
    if extra is not None:
        operands.append(extra)
        in_specs.append(pl.BlockSpec((block_n, Bp), lambda i: (i, 0)))
    grid = (xp.shape[0] // block_n,)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_n, Bp), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], Bp), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[:n, :B]


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _halfstep_impl(xi, t, marg, *, block_n: int, interpret: bool):
    mp = pad_axis(pad_axis(marg, 0, block_n, value=1.0), 1, LANE, value=1.0)
    return _matvec_like_call(_halfstep_kernel, xi, t, mp,
                             block_n=block_n, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_n", "interpret"))
def _matvec_impl(xi, t, *, block_n: int, interpret: bool):
    return _matvec_like_call(_matvec_kernel, xi, t, None,
                             block_n=block_n, interpret=interpret)


def _rows_blocks(xi, t, block_n, interpret, backend):
    return autotune.resolve_blocks(
        "feature_rows", {"n": xi.shape[0], "r": xi.shape[1],
                         "B": t.shape[1]},
        {"block_n": block_n}, xi.dtype, interpret, backend)


def sinkhorn_halfstep_pallas(
    xi: jax.Array,          # (n, r) features of the side being updated
    t: jax.Array,           # (r, B)
    marg: jax.Array,        # (n, B)
    *,
    block_n: Optional[int] = None,
    interpret: bool = False,
    backend: Optional[Backend] = None,
) -> jax.Array:
    """out = marg / (Xi @ t), shape (n, B). r rides whole in VMEM (r<=4096).

    Padded rows/columns: marg=1 so the divide yields finite garbage (or a
    harmless inf for all-zero feature rows) that the slice discards.
    """
    blocks = _rows_blocks(xi, t, block_n, interpret, backend)
    return _halfstep_impl(xi, t, marg, interpret=interpret, **blocks)


def feature_matvec_pallas(
    xi: jax.Array,          # (n, r)
    t: jax.Array,           # (r, B)
    *,
    block_n: Optional[int] = None,
    interpret: bool = False,
    backend: Optional[Backend] = None,
) -> jax.Array:
    """out = Xi @ t, shape (n, B) — no divide (marginal-check matvec)."""
    blocks = _rows_blocks(xi, t, block_n, interpret, backend)
    return _matvec_impl(xi, t, interpret=interpret, **blocks)


# ---------------------------------------------------------------------------
# Autotuner runners: execute one call at candidate blocks on synthetic
# device buffers of the keyed extents (see kernels.autotune).
# ---------------------------------------------------------------------------


def _contract_runner(extents, dtype, backend):
    xi = autotune._synthetic((extents["n"], extents["r"]), dtype)
    u = autotune._synthetic((extents["n"], extents["B"]), jnp.float32)
    impl = _feature_contract_splitk_impl if backend.split_reduce \
        else _feature_contract_impl

    def run(blocks):
        jax.block_until_ready(
            impl(xi, u, interpret=backend.interpret, **blocks))

    return run


def _rows_runner(extents, dtype, backend):
    xi = autotune._synthetic((extents["n"], extents["r"]), dtype)
    t = autotune._synthetic((extents["r"], extents["B"]), jnp.float32)
    marg = autotune._synthetic((extents["n"], extents["B"]), jnp.float32)

    def run(blocks):
        jax.block_until_ready(
            _halfstep_impl(xi, t, marg, interpret=backend.interpret,
                           **blocks))

    return run


autotune.register_runner("feature_contract", _contract_runner)
autotune.register_runner("feature_rows", _rows_runner)
