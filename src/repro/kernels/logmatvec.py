"""Pallas kernels: stabilized log-space factored Sinkhorn operators.

Three kernels cover the exact two-stage log-domain update (small-eps regime
where scalings under/overflow f32):

  * ``log_matvec_pallas``          — the original single-column row-LSE
        out_j = logsumexp_k( log_m[j, k] + t[k] )
    with EXACT per-row max stabilization (B = 1 keeps the joint max 2D).
  * ``log_feature_contract_pallas`` — stage 1 of the fused log iteration:
        t[k, c] = logsumexp_i( log_w[i, k] + s[i, c] )      (r, B)
    reduction over n via online ``logaddexp`` accumulation across n-blocks.
  * ``log_halfstep_pallas``         — stage 2 with the DIVIDE-FREE log
    half-step fused (the log-space twin of ``sinkhorn_halfstep_pallas``):
        out[j, c] = scale * ( lmarg[j, c] - logsumexp_k(log_w[j,k]+t[k,c]) )
    ``scale=eps`` yields the potential update  g = eps (log b - log K^T u);
    ``scale=-1, lmarg=0`` yields the raw LSE (the log plan's carried
    column log-marginal: convergence check and next g-update).

Stabilization in the B-column kernels is EXACT: the B loop is unrolled at
trace time (B is static) and each column takes a 2-D ``log_w + s[:, c]``
broadcast with the true joint max — identical numerics to the XLA
``logsumexp`` two-stage path, which is what makes the fused log hot loop
elementwise-match the operator path even at small eps where log entries
span hundreds of nats. B is therefore expected SMALL (the solvers run at
B = 1 and batch via vmap, which adds a leading Pallas grid axis); a
separable max-shift matmul would scale to wide B but underflows ~87 nats
below its bound, which is exactly the regime the log domain exists for.

Row-local stabilization happens inside the tile, so nothing quadratic ever
leaves VMEM. r rides whole per tile (r <= 4096 in all configs) and is
lane-padded with ``-inf`` (the logsumexp identity) via ``kernels.tiling``
then sliced back.

Backends: the row kernels are one parallel grid axis — they lower on
Mosaic and Triton unchanged. The stage-1 contraction's online-logaddexp
accumulation across n-blocks is a sequential-grid idiom; parallel-grid
backends (``split_reduce=True``) run the split-k variant — each grid cell
writes its own per-block partial LSE and XLA combines them with one final
``logsumexp`` over the block axis (LSE is associative, so the combine is
exact up to f32 rounding order). Block sizes resolve through
``kernels.autotune`` outside the jit boundary.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import autotune
from .backend import Backend
from .tiling import LANE, compute_f32 as _f32, pad_axis

__all__ = [
    "log_matvec_pallas",
    "log_feature_contract_pallas",
    "log_halfstep_pallas",
]


def _finite_or_zero(m: jax.Array) -> jax.Array:
    """Pin all-(-inf) shift rows/cols to 0 so ``x - m`` never produces NaN."""
    return jnp.where(jnp.isfinite(m), m, 0.0)


def _log_matvec_kernel(logm_ref, t_ref, o_ref):
    s = _f32(logm_ref[...]) + t_ref[...]              # (bm, r)
    m = jnp.max(s, axis=1, keepdims=True)             # exact joint row max
    m = _finite_or_zero(m)
    o_ref[...] = m + jnp.log(
        jnp.sum(jnp.exp(s - m), axis=1, keepdims=True)
    )


@functools.partial(jax.jit, static_argnames=("block_m", "interpret"))
def _log_matvec_impl(
    log_m: jax.Array,       # (m, r)
    t: jax.Array,           # (r,)
    *,
    block_m: int,
    interpret: bool,
) -> jax.Array:
    m, r = log_m.shape
    lp = pad_axis(pad_axis(log_m, 0, block_m, value=-jnp.inf),
                  1, LANE, value=-jnp.inf)
    tp = pad_axis(t, 0, LANE)       # added to -inf columns: fill irrelevant
    rp = lp.shape[1]
    grid = (lp.shape[0] // block_m,)
    out = pl.pallas_call(
        _log_matvec_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, rp), lambda i: (i, 0)),
            pl.BlockSpec((1, rp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((lp.shape[0], 1), jnp.float32),
        interpret=interpret,
    )(lp, tp[None, :])
    return out[:m, 0]


def log_matvec_pallas(
    log_m: jax.Array,       # (m, r)
    t: jax.Array,           # (r,)
    *,
    block_m: Optional[int] = None,
    interpret: bool = False,
    backend: Optional[Backend] = None,
) -> jax.Array:
    blocks = autotune.resolve_blocks(
        "log_rows", {"m": log_m.shape[0], "r": log_m.shape[1], "B": 1},
        {"block_m": block_m}, log_m.dtype, interpret, backend)
    return _log_matvec_impl(log_m, t, interpret=interpret, **blocks)


def _block_lse_cols(lw: jax.Array, s_ref, n_cols: int) -> jax.Array:
    """Per-column exact-joint-max LSE of one (bn, br) block: column c
    reduces ``lw + s[:, c]`` over axis 0. Returns (br, B)."""
    cols = []
    for c in range(n_cols):
        z = lw + s_ref[:, c][:, None]                  # (bn, br)
        m = _finite_or_zero(jnp.max(z, axis=0, keepdims=True))
        cols.append(
            (m + jnp.log(jnp.sum(jnp.exp(z - m), axis=0, keepdims=True)))[0]
        )                                              # (br,)
    return jnp.stack(cols, axis=1)                     # (br, B)


def _log_contract_kernel(lw_ref, s_ref, t_ref, *, n_cols: int):
    """t = logaddexp(t, LSE_i(lw_blk + s_blk)); n sequential grid axis.

    Per column c the (bn, br) broadcast ``lw + s[:, c]`` is reduced with
    its exact joint column max — B is unrolled at trace time."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        t_ref[...] = jnp.full_like(t_ref, -jnp.inf)

    contrib = _block_lse_cols(_f32(lw_ref[...]), s_ref, n_cols)
    t_ref[...] = jnp.logaddexp(t_ref[...], contrib)


def _log_contract_splitk_kernel(lw_ref, s_ref, t_ref, *, n_cols: int):
    """Split-k twin: cell (i, j) writes its own (1, br, B) partial LSE —
    no cross-program logaddexp, so the kernel lowers on parallel grids;
    the combine is one exact XLA ``logsumexp`` over the block axis."""
    t_ref[...] = _block_lse_cols(_f32(lw_ref[...]), s_ref, n_cols)[None]


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_r", "interpret")
)
def _log_contract_impl(
    log_w: jax.Array,       # (n, r) log-features
    s: jax.Array,           # (n, B) log-scalings (f / eps columns)
    *,
    block_n: int,
    block_r: int,
    interpret: bool,
) -> jax.Array:
    n, r = log_w.shape
    B = s.shape[1]
    lp = pad_axis(pad_axis(log_w, 0, block_n, value=-jnp.inf),
                  1, block_r, value=-jnp.inf)
    sp = pad_axis(s, 0, block_n, value=-jnp.inf)
    grid = (lp.shape[1] // block_r, lp.shape[0] // block_n)
    t = pl.pallas_call(
        functools.partial(_log_contract_kernel, n_cols=B),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_r), lambda i, j: (j, i)),
            pl.BlockSpec((block_n, B), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, B), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((lp.shape[1], B), jnp.float32),
        interpret=interpret,
    )(lp, sp)
    return t[:r]


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_r", "interpret")
)
def _log_contract_splitk_impl(
    log_w: jax.Array,
    s: jax.Array,
    *,
    block_n: int,
    block_r: int,
    interpret: bool,
) -> jax.Array:
    n, r = log_w.shape
    B = s.shape[1]
    lp = pad_axis(pad_axis(log_w, 0, block_n, value=-jnp.inf),
                  1, block_r, value=-jnp.inf)
    sp = pad_axis(s, 0, block_n, value=-jnp.inf)
    n_steps = lp.shape[0] // block_n
    grid = (lp.shape[1] // block_r, n_steps)
    partials = pl.pallas_call(
        functools.partial(_log_contract_splitk_kernel, n_cols=B),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, block_r), lambda i, j: (j, i)),
            pl.BlockSpec((block_n, B), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_r, B), lambda i, j: (j, i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_steps, lp.shape[1], B),
                                       jnp.float32),
        interpret=interpret,
    )(lp, sp)
    return jax.scipy.special.logsumexp(partials, axis=0)[:r]


def log_feature_contract_pallas(
    log_w: jax.Array,       # (n, r) log-features
    s: jax.Array,           # (n, B) log-scalings (f / eps columns)
    *,
    block_n: Optional[int] = None,
    block_r: Optional[int] = None,
    interpret: bool = False,
    split_reduce: bool = False,
    backend: Optional[Backend] = None,
) -> jax.Array:
    """t[k, c] = LSE_i(log_w[i, k] + s[i, c]), shape (r, B).

    The log-space twin of ``feature_contract_pallas``: -inf-padded rows
    are the LSE identity, so padding contributes nothing. B stays
    unpadded — the column loop is unrolled (B = 1 on the solver path).
    """
    n, r = log_w.shape
    blocks = autotune.resolve_blocks(
        "log_contract", {"n": n, "r": r, "B": s.shape[1]},
        {"block_n": block_n, "block_r": block_r}, log_w.dtype, interpret,
        backend)
    impl = _log_contract_splitk_impl if split_reduce else _log_contract_impl
    return impl(log_w, s, interpret=interpret, **blocks)


def _log_halfstep_kernel(lw_ref, t_ref, lmarg_ref, o_ref, *, scale: float,
                         n_cols: int):
    """o = scale * (lmarg - LSE_k(lw + t)) — LSE matvec + log half-step
    (subtract instead of divide) in one VMEM pass. Per column c the
    (bm, r) broadcast ``lw + t[:, c]`` takes its exact joint row max — B
    is unrolled at trace time."""
    lw = _f32(lw_ref[...])                             # (bm, r)
    cols = []
    for c in range(n_cols):
        z = lw + t_ref[:, c][None, :]                  # (bm, r)
        m = _finite_or_zero(jnp.max(z, axis=1, keepdims=True))
        lse = m + jnp.log(jnp.sum(jnp.exp(z - m), axis=1, keepdims=True))
        cols.append(lse[:, 0])                         # (bm,)
    lse_all = jnp.stack(cols, axis=1)                  # (bm, B)
    o_ref[...] = scale * (lmarg_ref[...] - lse_all)


@functools.partial(
    jax.jit, static_argnames=("scale", "block_m", "interpret")
)
def _log_halfstep_impl(
    log_w: jax.Array,       # (m, r) log-features of the side being updated
    t: jax.Array,           # (r, B) stage-1 output
    lmarg: jax.Array,       # (m, B) log target marginal (0 for raw LSE)
    *,
    scale: float,
    block_m: int,
    interpret: bool,
) -> jax.Array:
    m, r = log_w.shape
    B = t.shape[1]
    lp = pad_axis(pad_axis(log_w, 0, block_m, value=-jnp.inf),
                  1, LANE, value=-jnp.inf)
    tp = pad_axis(t, 0, LANE, value=-jnp.inf)
    mp = pad_axis(lmarg, 0, block_m)
    rp = tp.shape[0]
    grid = (lp.shape[0] // block_m,)
    out = pl.pallas_call(
        functools.partial(_log_halfstep_kernel, scale=scale, n_cols=B),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_m, rp), lambda i: (i, 0)),
            pl.BlockSpec((rp, B), lambda i: (0, 0)),
            pl.BlockSpec((block_m, B), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, B), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((lp.shape[0], B), jnp.float32),
        interpret=interpret,
    )(lp, tp, mp)
    return out[:m]


def log_halfstep_pallas(
    log_w: jax.Array,       # (m, r) log-features of the side being updated
    t: jax.Array,           # (r, B) stage-1 output
    lmarg: jax.Array,       # (m, B) log target marginal (0 for raw LSE)
    *,
    scale: float = 1.0,
    block_m: Optional[int] = None,
    interpret: bool = False,
    backend: Optional[Backend] = None,
) -> jax.Array:
    """out = scale * (lmarg - LSE_k(log_w[:, k] + t[k, :])), shape (m, B).

    The B-column generalization of :func:`log_matvec_pallas` with the
    divide-free log half-step fused: ``scale=eps`` gives the potential
    update ``eps (log b - log K^T e^{f/eps})`` directly; ``scale=-1`` with
    ``lmarg=0`` recovers the raw LSE. r rides whole in VMEM; B stays
    unpadded (unrolled columns, B = 1 on the solver path).
    """
    blocks = autotune.resolve_blocks(
        "log_rows", {"m": log_w.shape[0], "r": log_w.shape[1],
                     "B": t.shape[1]},
        {"block_m": block_m}, log_w.dtype, interpret, backend)
    return _log_halfstep_impl(log_w, t, lmarg, scale=scale,
                              interpret=interpret, **blocks)


# ---------------------------------------------------------------------------
# Autotuner runners
# ---------------------------------------------------------------------------


def _log_contract_runner(extents, dtype, backend):
    lw = autotune._synthetic((extents["n"], extents["r"]), dtype, log=True)
    s = autotune._synthetic((extents["n"], extents["B"]), jnp.float32,
                            log=True)
    impl = _log_contract_splitk_impl if backend.split_reduce \
        else _log_contract_impl

    def run(blocks):
        jax.block_until_ready(
            impl(lw, s, interpret=backend.interpret, **blocks))

    return run


def _log_rows_runner(extents, dtype, backend):
    lw = autotune._synthetic((extents["m"], extents["r"]), dtype, log=True)
    t = autotune._synthetic((extents["r"], extents["B"]), jnp.float32,
                            log=True)
    lmarg = autotune._synthetic((extents["m"], extents["B"]), jnp.float32,
                                log=True)

    def run(blocks):
        jax.block_until_ready(
            _log_halfstep_impl(lw, t, lmarg, scale=1.0,
                               interpret=backend.interpret, **blocks))

    return run


autotune.register_runner("log_contract", _log_contract_runner)
autotune.register_runner("log_rows", _log_rows_runner)
