"""Persistent multi-iteration Sinkhorn megakernel (scaling + log twins).

The per-iteration fused plan (``kernels.ops``) still pays 4-5 Pallas/XLA
dispatches per Sinkhorn iteration and round-trips ``u/v`` (resp. ``f/g``)
and every intermediate through HBM. ``BENCH_seed.json`` puts the resulting
hot loop at 0.16-0.39 GFLOP/s — dispatch and memory traffic, not FLOPs.
This module collapses ``inner_steps`` FULL iterations into ONE
``pallas_call``:

  * Xi/Zeta are fetched from HBM exactly once per launch and stay resident
    in VMEM for all ``inner_steps`` iterations (whole-array blocks; the
    plan layer only selects this kernel when the working set fits the VMEM
    budget — larger shapes keep the streaming per-iteration plan),
  * ``u/v`` (scaling mode) resp. ``f/g`` and the stage-1 LSE carry (log
    mode) live entirely on-chip across iterations — the ``lax.fori_loop``
    runs INSIDE the kernel body,
  * the marginal error is computed once, at the block boundary, and is the
    only scalar that leaves the chip per block.

Numerics are the per-iteration plan's, step for step: the same
``s = Zeta^T (Xi^T u)`` carry reuse in scaling mode (log mode carries the
stage-1 LSE, the per-iteration plan the column log-marginal it feeds), the
same momentum relaxations, the same exact joint-max LSE stabilization in
log mode — so a block of ``inner_steps`` megakernel iterations matches
``inner_steps`` unfused plan steps elementwise at the block boundary
(single-tile shapes; multi-tile shapes differ only by f32 summation
order).

Mixed precision: feature operands may arrive in bf16 (the
``precision="bf16"`` execution policy — half the HBM stream). Kernels
upcast feature tiles to f32 in registers; every contraction and LSE
accumulates in f32.

On CPU (CI) the kernels run in ``interpret=True`` mode; on TPU the same
bodies compile to Mosaic under a scoped-VMEM limit equal to the backend
record's admission budget. ``relax_scaling`` / ``relax_log`` are canonical
here (shared with the XLA solvers through ``kernels.ops``) so this module
stays import-cycle-free.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import Backend
from .logmatvec import _finite_or_zero
from .tiling import (F32_PRODUCTS, LANE, compute_f32 as _f32, pad_axis,
                     round_up)

__all__ = [
    "relax_scaling",
    "relax_log",
    "block_vmem_bytes",
    "block_plan_fits",
    "sinkhorn_block_pallas",
    "log_sinkhorn_block_pallas",
]

# sublane quantum covering both f32 (8) and bf16 (16) second-to-minor dims
_SUBLANE_ANY = 16


# ---------------------------------------------------------------------------
# Over-relaxation (canonical definitions; re-exported by kernels.ops)
# ---------------------------------------------------------------------------


def relax_scaling(new: jax.Array, old: jax.Array,
                  momentum: float) -> jax.Array:
    """Geometric over-relaxation  u <- old^{1-w} * new^w  (Thibault et al.),
    the scaling-space form. ``momentum`` is a trace-time constant.

    Zero scalings (zero-weight / bucket-padded atoms pin u = 0 from the
    first iteration) bypass the blend: for w > 1 the geometric mean hits
    0^{1-w} = inf and 0 * inf = NaN, which would poison the marginal error
    and silently stop the while_loop. Masked entries take ``new`` verbatim
    — the exact twin of the -inf guard in :func:`relax_log`."""
    if momentum == 1.0:
        return new
    mixed = old ** (1.0 - momentum) * new ** momentum
    return jnp.where((old > 0) & (new > 0), mixed, new)


def relax_log(new: jax.Array, old: jax.Array, momentum: float) -> jax.Array:
    """Log-space over-relaxation  f <- (1-w) old + w new  — the exact log of
    the geometric scaling relaxation. Atoms whose potential is pinned at
    -inf (zero weight) bypass the blend: (1-w)*(-inf) + w*(-inf) is NaN for
    w > 1, so the masked entries take ``new`` verbatim."""
    if momentum == 1.0:
        return new
    mixed = (1.0 - momentum) * old + momentum * new
    return jnp.where(jnp.isfinite(old) & jnp.isfinite(new), mixed, new)


# ---------------------------------------------------------------------------
# VMEM budget policy
# ---------------------------------------------------------------------------


def block_vmem_bytes(n: int, m: int, r: int, B: int = 1,
                     feature_dtype=jnp.float32) -> int:
    """VMEM bytes one megakernel launch may need, counted as Mosaic lays
    the operands out (measured against the v5e compiler, which
    ``tests/test_tpu_compile.py`` keeps honest).

    * A carried (rows, B) f32 vector is tiled ``T(8, 128)``: its minor
      B axis pads to a full lane, so each row costs ``round_up(B, 128) * 4``
      bytes -- 128x the unpadded count at B = 1.
    * The kernel loads every factor tile as f32 (``_f32``), so bf16 factors
      need an f32 working copy on top of their storage.
    * Launched under ``vmap`` (solve_many, the service's bucket runners)
      the call gains a grid axis and every operand block is double
      buffered, so the inputs and outputs count twice.

    Per launch: 3 n-length and 5 m-length carries in and out (the log twin
    has one fewer m-carry but two (r, B) stage-1 LSE carries), plus about
    three vectors' worth of loop temporaries per support row.
    """
    np_, mp = round_up(n, _SUBLANE_ANY), round_up(m, _SUBLANE_ANY)
    rows, rp = np_ + mp, round_up(r, LANE)
    vec_row = round_up(B, LANE) * 4
    factors = rows * rp * jnp.dtype(feature_dtype).itemsize
    io = factors + (3 * np_ + 5 * mp + 2 * rp) * vec_row
    work = rows * rp * 4 + 3 * rows * vec_row
    return 2 * io + work


def block_plan_fits(n: int, m: int, r: int, B: int = 1,
                    feature_dtype=jnp.float32, *,
                    backend: Backend) -> bool:
    """Whether the whole-array megakernel is admissible at this shape: the
    :class:`~repro.kernels.backend.Backend` record's budget bounds
    :func:`block_vmem_bytes`, and records whose megakernel lowering is
    disabled refuse outright. On tpu-mosaic the same budget is the
    compiler's scoped-VMEM limit (:func:`_call_params`)."""
    return (backend.megakernel
            and block_vmem_bytes(n, m, r, B, feature_dtype)
            <= backend.block_budget)


def _call_params(backend: Backend) -> dict:
    """``pallas_call`` options of one megakernel launch. On Mosaic the
    scoped-VMEM limit IS the record's admission budget, so the bytes
    :func:`block_plan_fits` admits are the bytes the compiler may use."""
    if backend.interpret:
        return dict(interpret=True)
    if backend.name == "tpu-mosaic":
        return dict(compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=backend.block_budget))
    return {}


def _pad_rows_rep(arr: jax.Array, mult: int) -> jax.Array:
    """Pad axis 0 to a multiple of ``mult`` by REPLICATING the last row.

    Scaling-mode feature pads must stay strictly positive (a zero feature
    row paired with the padded atom's a = 0 weight would divide 0/0); a
    replicated row keeps ``Xi @ t > 0`` while the zero-weight pairing pins
    the padded scaling to exactly 0 — the bucket-padding contract."""
    pad = (-arr.shape[0]) % mult
    if pad == 0:
        return arr
    tail = jnp.broadcast_to(arr[-1:], (pad,) + arr.shape[1:])
    return jnp.concatenate([arr, tail], axis=0)


# ---------------------------------------------------------------------------
# Scaling-mode megakernel
# ---------------------------------------------------------------------------


def _contract(w: jax.Array, x: jax.Array) -> jax.Array:
    """(n, r)^T @ (n, B) -> (r, B), f32 accumulation."""
    return jax.lax.dot_general(
        w, x, (((0,), (0,)), ((), ())), precision=F32_PRODUCTS,
        preferred_element_type=jnp.float32,
    )


def _matvec(w: jax.Array, t: jax.Array) -> jax.Array:
    """(n, r) @ (r, B) -> (n, B), f32 accumulation."""
    return jax.lax.dot_general(
        w, t, (((1,), (0,)), ((), ())), precision=F32_PRODUCTS,
        preferred_element_type=jnp.float32,
    )


def _block_kernel(xi_ref, zeta_ref, a_ref, b_ref, u0_ref, v0_ref, s0_ref,
                  u_ref, v_ref, s_ref, err_ref, *, inner_steps: int,
                  momentum: float):
    """``inner_steps`` full Alg.-1 iterations, all carries on-chip.

    Identical step semantics to the per-iteration plan
    (``ops._scaling_plan``): carry (u, v, s = Zeta^T (Xi^T u)); the
    marginal error |v . s - b|_1 is emitted once, at the block boundary.
    Padded support rows are exact zero-weight atoms (b = 0, v = 0), so
    they contribute exactly 0 to the reduction. B is UNPADDED: unlike
    one-shot kernels — whose garbage pad-lane outputs get sliced after a
    single pass — the megakernel feeds its lanes back into the next
    on-chip iteration, where a zero-filled marginal column would turn
    into 0/0 NaN on the second step; and padding B to a full lane would
    multiply the on-chip carry footprint 128x for the solver's B = 1.
    """
    xi = _f32(xi_ref[...])          # (n, r) — VMEM-resident for the block
    zeta = _f32(zeta_ref[...])      # (m, r)
    a = a_ref[...]
    b = b_ref[...]

    def one(_, carry):
        u, v, s = carry
        v_new = relax_scaling(b / s, v, momentum)
        t = _contract(zeta, v_new)                    # (r, B)
        u_new = relax_scaling(a / _matvec(xi, t), u, momentum)
        t2 = _contract(xi, u_new)                     # (r, B)
        s_new = _matvec(zeta, t2)                     # (m, B)
        return u_new, v_new, s_new

    u, v, s = jax.lax.fori_loop(
        0, inner_steps, one, (u0_ref[...], v0_ref[...], s0_ref[...])
    )
    u_ref[...] = u
    v_ref[...] = v
    s_ref[...] = s
    err_ref[...] = jnp.sum(jnp.abs(v * s - b), keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("inner_steps", "momentum", "backend")
)
def sinkhorn_block_pallas(
    xi: jax.Array,          # (n, r) features (f32 or bf16 storage)
    zeta: jax.Array,        # (m, r)
    a: jax.Array,           # (n, B) target marginals (zeros = dead atoms)
    b: jax.Array,           # (m, B)
    u0: jax.Array,          # (n, B) scaling carry at block entry
    v0: jax.Array,          # (m, B)
    s0: jax.Array,          # (m, B) carried  s = Zeta^T (Xi^T u0)
    *,
    inner_steps: int,
    momentum: float = 1.0,
    backend: Backend,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One megakernel block: ``inner_steps`` scaling-space iterations.

    Returns ``(u, v, s, err)`` — the plan-step carry after the block plus
    the block-boundary marginal error (a scalar). Padding: feature rows
    replicate (positive), weights/scalings pad 0 (inert zero-weight
    atoms), ``s0`` pads 1 (divide-safe; the padded v stays 0 because its b
    is 0), so padded lanes contribute exactly nothing to the carries or
    the error.
    """
    n, r = xi.shape
    m = zeta.shape[0]
    B = a.shape[1]
    xp = _pad_rows_rep(pad_axis(xi, 1, LANE), _SUBLANE_ANY)
    zp = _pad_rows_rep(pad_axis(zeta, 1, LANE), _SUBLANE_ANY)
    ap = pad_axis(a, 0, _SUBLANE_ANY)
    bp = pad_axis(b, 0, _SUBLANE_ANY)
    up = pad_axis(u0, 0, _SUBLANE_ANY)
    vp = pad_axis(v0, 0, _SUBLANE_ANY)
    sp = pad_axis(s0, 0, _SUBLANE_ANY, value=1.0)
    npad, mpad = xp.shape[0], zp.shape[0]
    u, v, s, err = pl.pallas_call(
        functools.partial(_block_kernel, inner_steps=inner_steps,
                          momentum=momentum),
        out_shape=(
            jax.ShapeDtypeStruct((npad, B), jnp.float32),
            jax.ShapeDtypeStruct((mpad, B), jnp.float32),
            jax.ShapeDtypeStruct((mpad, B), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        **_call_params(backend),
    )(xp, zp, ap, bp, up, vp, sp)
    return u[:n], v[:m], s[:m], err[0, 0]


# ---------------------------------------------------------------------------
# Log-mode megakernel (small-eps twin)
# ---------------------------------------------------------------------------


def _lse_rows(lw: jax.Array, t: jax.Array, n_cols: int) -> jax.Array:
    """out[j, c] = LSE_k(lw[j, k] + t[k, c]) with the exact per-column
    joint max (B unrolled at trace time — B = 1 on the solver path)."""
    cols = []
    for c in range(n_cols):
        z = lw + t[:, c][None, :]                      # (m, r)
        mx = _finite_or_zero(jnp.max(z, axis=1, keepdims=True))
        cols.append(
            (mx + jnp.log(jnp.sum(jnp.exp(z - mx), axis=1,
                                  keepdims=True)))[:, 0]
        )
    return jnp.stack(cols, axis=1)                     # (m, B)


def _lse_contract(lw: jax.Array, s: jax.Array, n_cols: int) -> jax.Array:
    """out[k, c] = LSE_i(lw[i, k] + s[i, c]) — the stage-1 contraction."""
    cols = []
    for c in range(n_cols):
        z = lw + s[:, c][:, None]                      # (n, r)
        mx = _finite_or_zero(jnp.max(z, axis=0, keepdims=True))
        cols.append(
            (mx + jnp.log(jnp.sum(jnp.exp(z - mx), axis=0,
                                  keepdims=True)))[0]
        )
    return jnp.stack(cols, axis=1)                     # (r, B)


def _log_block_kernel(lxi_ref, lzt_ref, loga_ref, logb_ref, b_ref,
                      f0_ref, g0_ref, t0_ref, f_ref, g_ref, t_ref, err_ref,
                      *, inner_steps: int, eps: float, momentum: float,
                      n_cols: int):
    """``inner_steps`` full log-domain iterations on-chip.

    The carry is (f, g, t1) with t1 = LSE_i(logXi + f/eps): each
    iteration takes the g-update's second LSE stage from the VMEM-resident
    factors, and the marginal check runs at the block boundary only. The
    per-iteration step (``ops._log_plan``) carries that second stage
    instead, ``log(K^T e^{f/eps}) = _lse_rows(lzt, t1)``, so the two
    carries differ in their third element; ``(f, g)`` and the error match
    at block boundaries. The B columns are UNROLLED at trace time with the
    exact per-column joint max (the ``logmatvec`` stabilization contract),
    so B stays unpadded — B = 1 on the solver path, batching rides the
    vmap grid axis.
    """
    lxi = _f32(lxi_ref[...])        # (n, r) log-features, VMEM-resident
    lzt = _f32(lzt_ref[...])        # (m, r)
    loga = loga_ref[...]
    logb = logb_ref[...]

    def one(_, carry):
        f, g, t1 = carry
        g_new = relax_log(eps * (logb - _lse_rows(lzt, t1, n_cols)),
                          g, momentum)
        t2 = _lse_contract(lzt, g_new / eps, n_cols)
        f_new = relax_log(eps * (loga - _lse_rows(lxi, t2, n_cols)),
                          f, momentum)
        t3 = _lse_contract(lxi, f_new / eps, n_cols)
        return f_new, g_new, t3

    f, g, t = jax.lax.fori_loop(
        0, inner_steps, one, (f0_ref[...], g0_ref[...], t0_ref[...])
    )
    f_ref[...] = f
    g_ref[...] = g
    t_ref[...] = t
    log_col = _lse_rows(lzt, t, n_cols) + g / eps
    err_ref[...] = jnp.sum(jnp.abs(jnp.exp(log_col) - b_ref[...]),
                           keepdims=True)


@functools.partial(
    jax.jit, static_argnames=("inner_steps", "eps", "momentum", "backend")
)
def log_sinkhorn_block_pallas(
    log_xi: jax.Array,      # (n, r) log-features (f32 or bf16 storage)
    log_zeta: jax.Array,    # (m, r)
    loga: jax.Array,        # (n, B) masked-log weights (-inf = dead atom)
    logb: jax.Array,        # (m, B)
    b: jax.Array,           # (m, B) linear column marginal (error check)
    f0: jax.Array,          # (n, B) potential carry at block entry
    g0: jax.Array,          # (m, B)
    t0: jax.Array,          # (r, B) carried stage-1 LSE of f0
    *,
    inner_steps: int,
    eps: float,
    momentum: float = 1.0,
    backend: Backend,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One megakernel block: ``inner_steps`` log-domain iterations.

    Returns ``(f, g, t, err)`` — the megakernel's carry after the block
    plus the block-boundary marginal error. Padding: support rows
    replicate the last log-feature row while their weights/potentials pad
    ``-inf`` (the LSE identity) and the linear ``b`` pads 0 — exact
    zero-weight atoms end to end; the feature minor (r) axis pads
    ``-inf``.
    """
    n, r = log_xi.shape
    m = log_zeta.shape[0]
    B = loga.shape[1]
    ninf = -jnp.inf
    xp = _pad_rows_rep(pad_axis(log_xi, 1, LANE, value=ninf), _SUBLANE_ANY)
    zp = _pad_rows_rep(pad_axis(log_zeta, 1, LANE, value=ninf),
                       _SUBLANE_ANY)
    # B stays UNPADDED (columns are trace-time unrolled; B = 1 on the
    # solver path) — only the support rows and the feature/LSE minor dim
    # take lane padding, all with the -inf LSE identity.
    lap = pad_axis(loga, 0, _SUBLANE_ANY, value=ninf)
    lbp = pad_axis(logb, 0, _SUBLANE_ANY, value=ninf)
    bp = pad_axis(b, 0, _SUBLANE_ANY)
    fp = pad_axis(f0, 0, _SUBLANE_ANY, value=ninf)
    gp = pad_axis(g0, 0, _SUBLANE_ANY, value=ninf)
    tp = pad_axis(t0, 0, LANE, value=ninf)
    npad, mpad = xp.shape[0], zp.shape[0]
    rpad = tp.shape[0]
    f, g, t, err = pl.pallas_call(
        functools.partial(_log_block_kernel, inner_steps=inner_steps,
                          eps=eps, momentum=momentum, n_cols=B),
        out_shape=(
            jax.ShapeDtypeStruct((npad, B), jnp.float32),
            jax.ShapeDtypeStruct((mpad, B), jnp.float32),
            jax.ShapeDtypeStruct((rpad, B), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        **_call_params(backend),
    )(xp, zp, lap, lbp, bp, fp, gp, tp)
    return f[:n], g[:m], t[:r], err[0, 0]
