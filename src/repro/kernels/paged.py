"""Pallas kernels for PAGED feature storage: masked matvecs that skip
all-dead pages.

The streaming layer (``repro.streaming``) keeps each distribution's
features in a fixed-capacity buffer carved into pages of ``page_size``
rows; insert/evict mutate pages and flip weights, never array shapes, so
nothing retraces. Dead slots carry zero weight — which every solver masks
exactly — so correctness never depends on the page table. What the page
table buys is a FAST PATH: a per-page liveness vector (``page_live``,
scalar-prefetched into SMEM) lets the kernels predicate whole page blocks
with ``pl.when`` and skip the MXU work for pages with no live slot at all.
A store at 25% occupancy then streams ~25% of the feature bytes per
iteration instead of 100%.

Three kernels mirror the dense trio in ``kermatvec``:

  paged_feature_contract : t = sum over LIVE pages of Xi_p^T u_p   (r, B)
  paged_halfstep         : out_p = marg_p / (Xi_p @ t) on live pages,
                           zeros on dead ones (marg is 0 there anyway)
  paged_feature_matvec   : the divide-free twin (convergence marginal)

All three are ELEMENTWISE equal to their unpaged twins whenever the dead
slots carry zero weight/scaling — property-tested in
``tests/test_streaming.py`` — because a dead slot's u/v is 0 (scaling
space), so a skipped page contributes exactly the 0 the dense kernel would
have computed.

Backend notes: the contract kernel accumulates across the page grid into
one revisited output block — the sequential-grid idiom only Mosaic (and
interpret mode) supports. Parallel-grid backends (``split_reduce=True``,
i.e. gpu-triton) have no paged fast path yet; callers (``ops.geometry_ops``)
fall back to the flat kernels / XLA masked operators there — a refusal,
never a silent interpret (the PR 7 rule).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import Backend
from .tiling import F32_PRODUCTS, LANE, compute_f32 as _f32, pad_axis

__all__ = [
    "paged_feature_contract_pallas",
    "paged_halfstep_pallas",
    "paged_feature_matvec_pallas",
    "paged_contract_ref",
    "paged_matvec_ref",
    "paged_supported",
]


def paged_supported(backend: Optional[Backend]) -> bool:
    """Whether the paged fast path lowers on ``backend``: the contract
    kernel needs a sequential accumulation grid (Mosaic / interpret)."""
    return backend is None or not backend.split_reduce


def _check_paged(n: int, page_size: int, n_pages: int) -> None:
    if page_size % 8 != 0:
        raise ValueError(
            f"page_size must be a multiple of the f32 sublane (8), got "
            f"{page_size}"
        )
    if n != page_size * n_pages:
        raise ValueError(
            f"capacity {n} != page_size {page_size} * n_pages {n_pages}; "
            "paged buffers are exact multiples of the page granularity"
        )


# ---------------------------------------------------------------------------
# Contract: t = Xi^T u over live pages only
# ---------------------------------------------------------------------------


def _paged_contract_kernel(live_ref, xi_ref, u_ref, t_ref):
    """t += Xi_p^T u_p for live pages; dead pages skip the dot entirely.

    The page axis is the (sequential) grid; ``live_ref`` is the
    scalar-prefetched per-page live count in SMEM, so the predicate is
    known before the page's feature block is even needed."""
    p = pl.program_id(0)

    @pl.when(p == 0)
    def _init():
        t_ref[...] = jnp.zeros_like(t_ref)

    @pl.when(live_ref[p] > 0)
    def _acc():
        t_ref[...] += jax.lax.dot_general(
            _f32(xi_ref[...]),
            u_ref[...],
            (((0,), (0,)), ((), ())),          # contract the page-row axis
            precision=F32_PRODUCTS,
            preferred_element_type=jnp.float32,
        )


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def _paged_contract_impl(
    xi: jax.Array,          # (C, r) paged feature buffer
    u: jax.Array,           # (C, B)
    page_live: jax.Array,   # (n_pages,) int32 live-slot counts
    *,
    page_size: int,
    interpret: bool,
) -> jax.Array:
    C, r = xi.shape
    B = u.shape[1]
    xp = pad_axis(xi, 1, LANE)
    up = pad_axis(u, 1, LANE)
    rp, Bp = xp.shape[1], up.shape[1]
    n_pages = C // page_size
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_pages,),
        in_specs=[
            pl.BlockSpec((page_size, rp), lambda p, live: (p, 0)),
            pl.BlockSpec((page_size, Bp), lambda p, live: (p, 0)),
        ],
        out_specs=pl.BlockSpec((rp, Bp), lambda p, live: (0, 0)),
    )
    t = pl.pallas_call(
        _paged_contract_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rp, Bp), jnp.float32),
        interpret=interpret,
    )(page_live, xp, up)
    return t[:r, :B]


def paged_feature_contract_pallas(
    xi: jax.Array,          # (C, r)
    u: jax.Array,           # (C, B)
    page_live: jax.Array,   # (n_pages,) int32
    *,
    page_size: int,
    interpret: bool = False,
    backend: Optional[Backend] = None,
) -> jax.Array:
    """t = Xi^T u over live pages, shape (r, B).

    Exact vs the dense contract whenever dead slots carry u = 0 (the
    zero-weight masking invariant); all-dead pages are skipped, so a
    sparse store streams only its live pages' bytes."""
    _check_paged(xi.shape[0], page_size, page_live.shape[0])
    return _paged_contract_impl(xi, u, page_live, page_size=page_size,
                                interpret=interpret)


# ---------------------------------------------------------------------------
# Row kernels: halfstep / matvec with dead pages writing zeros
# ---------------------------------------------------------------------------


def _paged_halfstep_kernel(live_ref, xi_ref, t_ref, marg_ref, o_ref):
    p = pl.program_id(0)

    @pl.when(live_ref[p] > 0)
    def _live():
        kv = jax.lax.dot_general(
            _f32(xi_ref[...]),
            t_ref[...],
            (((1,), (0,)), ((), ())),
            precision=F32_PRODUCTS,
            preferred_element_type=jnp.float32,
        )
        o_ref[...] = marg_ref[...] / kv

    @pl.when(live_ref[p] == 0)
    def _dead():
        # a dead slot's marginal is 0 and the kernel is positive, so the
        # dense quotient is 0 too — writing zeros IS the exact value
        o_ref[...] = jnp.zeros_like(o_ref)


def _paged_matvec_kernel(live_ref, xi_ref, t_ref, o_ref):
    p = pl.program_id(0)

    @pl.when(live_ref[p] > 0)
    def _live():
        o_ref[...] = jax.lax.dot_general(
            _f32(xi_ref[...]),
            t_ref[...],
            (((1,), (0,)), ((), ())),
            precision=F32_PRODUCTS,
            preferred_element_type=jnp.float32,
        )

    @pl.when(live_ref[p] == 0)
    def _dead():
        # dead rows' matvec output is only ever consumed multiplied by a
        # zero scaling/weight; zeros keep it finite (and skip the MXU)
        o_ref[...] = jnp.zeros_like(o_ref)


def _paged_rows_call(kernel, xi, t, extra, page_live, *, page_size,
                     interpret):
    C, r = xi.shape
    B = t.shape[1]
    xp = pad_axis(xi, 1, LANE)
    tp = pad_axis(pad_axis(t, 0, LANE), 1, LANE)
    rp, Bp = tp.shape
    operands = [page_live, xp, tp]
    in_specs = [
        pl.BlockSpec((page_size, rp), lambda p, live: (p, 0)),
        pl.BlockSpec((rp, Bp), lambda p, live: (0, 0)),
    ]
    if extra is not None:
        operands.append(extra)
        in_specs.append(pl.BlockSpec((page_size, Bp), lambda p, live: (p, 0)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(C // page_size,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((page_size, Bp), lambda p, live: (p, 0)),
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((C, Bp), jnp.float32),
        interpret=interpret,
    )(*operands)
    return out[:, :B]


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def _paged_halfstep_impl(xi, t, marg, page_live, *, page_size: int,
                         interpret: bool):
    mp = pad_axis(marg, 1, LANE, value=1.0)
    return _paged_rows_call(_paged_halfstep_kernel, xi, t, mp, page_live,
                            page_size=page_size, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("page_size", "interpret"))
def _paged_matvec_impl(xi, t, page_live, *, page_size: int, interpret: bool):
    return _paged_rows_call(_paged_matvec_kernel, xi, t, None, page_live,
                            page_size=page_size, interpret=interpret)


def paged_halfstep_pallas(
    xi: jax.Array,          # (C, r)
    t: jax.Array,           # (r, B)
    marg: jax.Array,        # (C, B) target marginal (0 on dead slots)
    page_live: jax.Array,   # (n_pages,) int32
    *,
    page_size: int,
    interpret: bool = False,
    backend: Optional[Backend] = None,
) -> jax.Array:
    """out = marg / (Xi @ t) on live pages, zeros on all-dead pages."""
    _check_paged(xi.shape[0], page_size, page_live.shape[0])
    return _paged_halfstep_impl(xi, t, marg, page_live,
                                page_size=page_size, interpret=interpret)


def paged_feature_matvec_pallas(
    xi: jax.Array,          # (C, r)
    t: jax.Array,           # (r, B)
    page_live: jax.Array,   # (n_pages,) int32
    *,
    page_size: int,
    interpret: bool = False,
    backend: Optional[Backend] = None,
) -> jax.Array:
    """out = Xi @ t on live pages, zeros on all-dead pages (no divide)."""
    _check_paged(xi.shape[0], page_size, page_live.shape[0])
    return _paged_matvec_impl(xi, t, page_live, page_size=page_size,
                              interpret=interpret)


# ---------------------------------------------------------------------------
# XLA references (parity oracles + the fallback the geometry's operators use)
# ---------------------------------------------------------------------------


def paged_contract_ref(xi, u, page_live, *, page_size: int) -> jax.Array:
    """Masked XLA twin of :func:`paged_feature_contract_pallas`."""
    C, r = xi.shape
    n_pages = C // page_size
    mask = jnp.repeat((page_live > 0).astype(xi.dtype), page_size)
    return jnp.matmul(_f32(xi).T, u * mask[:, None], precision=F32_PRODUCTS)


def paged_matvec_ref(xi, t, page_live, *, page_size: int) -> jax.Array:
    """Masked XLA twin of :func:`paged_feature_matvec_pallas`."""
    mask = jnp.repeat((page_live > 0).astype(xi.dtype), page_size)
    return jnp.matmul(_f32(xi), t, precision=F32_PRODUCTS) * mask[:, None]
