"""Plain reference of a balanced, log-domain Sinkhorn solve between point
clouds under Lemma 1's Gaussian positive features (Scetbon & Cuturi 2020).

It imports nothing of the program under test and uses nothing the program
made: points, anchors, weights and the data radius come from the benchmark.

    log Xi[i, k] = c_k - log(r) / 2 - (2 / eps) ||x_i - u_k||^2
    c_k          = (d / 4) log(2 q) + ||u_k||^2 / (q eps)
    K            = Xi Zeta^T

``check`` reads one returned answer (potentials f, g and the cost) against
that kernel, in blocks of rows so that any size fits. With
``P = diag(e^{f/eps}) K diag(e^{g/eps})``:

* ``row_err``: the L1 error of P's row marginal once its total mass is
  divided out (its shape), plus the relative gap between the returned
  cost and ``<a, f> + <b, g>`` (Eq. 6's dual value, over its size or over
  eps where that is larger). The iteration ends on an f-update, which
  makes the row marginal exact under the solver's own kernel whatever the
  tolerance, so this reads how far the solver's kernel is from the
  reference's, entry by entry, and whether the cost is that of the
  potentials;
* ``col_err``: the L1 error of P's column marginal, the residual the
  solver stops on, mass included: it reads whether the answer was solved
  to tol under the reference kernel.

Both are computed in float64 on the host, so that the reading does not
carry the accelerator's float32 exponentials.

``solve`` is the same iteration the program runs (g-update, f-update, the
column marginal's L1 error checked every iteration), in plain
``jax.numpy``. With its features' cross term at ``bf16_3x``, the precision
next below the configuration's float32 at HIGHEST, it is the benchmark's
control.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import logsumexp

HIGHEST = "highest"
BF16_3X = "bf16_3x"
BLOCK = 65536

__all__ = ["HIGHEST", "BF16_3X", "gaussian_q", "log_features", "check", "solve",
           "Result"]


def _lambert_w0(z: float) -> float:
    w = math.log1p(z)
    for _ in range(100):
        e = math.exp(w)
        step = (w * e - z) / (e * (w + 1.0))
        w -= step
        if abs(step) < 1e-15 * max(1.0, abs(w)):
            break
    return w


def gaussian_q(R: float, eps: float, d: int) -> float:
    z = R * R / eps / d
    return 0.5 if z == 0.0 else z / (2.0 * _lambert_w0(z))


def _bf16(v):
    """v rounded to bfloat16's 8-bit mantissa, kept in v's type (an explicit
    rounding no compiler may drop as excess precision)."""
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


def _split(v):
    hi = _bf16(v)
    return hi, _bf16(v - hi)


def cross(x, anchors, precision=HIGHEST):
    """x @ anchors.T in float32 ("highest"), or as the three bfloat16
    products a TPU's ``Precision.HIGH`` makes ("bf16_3x"), spelled out so
    that every backend computes the same thing."""
    if precision == HIGHEST:
        return jnp.dot(x, anchors.T, precision=jax.lax.Precision.HIGHEST)
    if precision != BF16_3X:
        raise ValueError(f"precision must be {HIGHEST!r} or {BF16_3X!r}")
    xh, xl = _split(x)
    uh, ul = _split(anchors)
    dot = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    return dot(xh, uh.T) + (dot(xh, ul.T) + dot(xl, uh.T))


def log_features(x, anchors, *, eps: float, q: float, precision=HIGHEST):
    """log Xi, (n, r), with the cross term at ``precision``."""
    d, r = anchors.shape[1], anchors.shape[0]
    u2 = jnp.sum(anchors * anchors, axis=1)
    c = 0.25 * d * math.log(2.0 * q) + u2 / (q * eps) - 0.5 * math.log(r)
    xu = cross(x, anchors, precision)
    sq = jnp.sum(x * x, axis=1)[:, None] + u2[None, :] - 2.0 * xu
    return c[None, :] - (2.0 / eps) * sq


def _blocks(arr, block):
    n = arr.shape[0]
    return arr.reshape((n // block, block) + arr.shape[1:])


@functools.partial(jax.jit, static_argnames=("eps", "q", "block"))
def _stage(p, pot, anchors, *, eps, q, block):
    """t_k = LSE_i (log Xi[i, k] + pot_i / eps) over all rows of p, in blocks."""
    def body(acc, blk):
        pts, w = blk
        lf = log_features(pts, anchors, eps=eps, q=q)
        return jnp.logaddexp(acc, logsumexp(lf + w[:, None] / eps, axis=0)), None
    init = jnp.full((anchors.shape[0],), -jnp.inf, pot.dtype)
    out, _ = jax.lax.scan(body, init, (_blocks(p, block), _blocks(pot, block)))
    return out


@functools.partial(jax.jit, static_argnames=("eps", "q", "block"))
def _marginal(p, pot, t, anchors, *, eps, q, block):
    """exp(pot_i / eps + LSE_k(log Xi[i, k] + t_k)) for every row, in blocks."""
    def body(_, blk):
        pts, f = blk
        lf = log_features(pts, anchors, eps=eps, q=q)
        return None, jnp.exp(f / eps + logsumexp(lf + t[None, :], axis=1))
    _, out = jax.lax.scan(body, None, (_blocks(p, block), _blocks(pot, block)))
    return out.reshape(-1)


def _uniform(n, dtype=jnp.float32):
    return jnp.full((n,), 1.0 / n, dtype)


def check(x, y, anchors, f, g, cost, *, eps: float, R: float, a=None,
          b=None) -> dict:
    """Read one answer against the reference kernel, in float64 on the host.

    Returns ``row_err`` and ``col_err`` (see the module's docstring) and,
    for the record: ``mass_err`` (|sum P - 1|), ``row_l1`` (the row
    marginal's L1 error before the mass is taken out), ``cost_gap`` and
    ``dual``. Runs on the CPU, in blocks of at most ``BLOCK`` rows.
    """
    cpu = jax.devices("cpu")[0]
    n, m = x.shape[0], y.shape[0]
    bn, bm = min(BLOCK, n), min(BLOCK, m)
    if n % bn or m % bm:
        raise ValueError(f"sizes {n}, {m} are not multiples of {BLOCK}")
    q = gaussian_q(R, eps, x.shape[1])
    a64 = np.full(n, 1.0 / n) if a is None else np.asarray(a, np.float64)
    b64 = np.full(m, 1.0 / m) if b is None else np.asarray(b, np.float64)
    f64, g64 = np.asarray(f, np.float64), np.asarray(g, np.float64)
    with jax.enable_x64(True):
        x, y, anchors, f, g = (jax.device_put(np.asarray(v, np.float64), cpu)
                               for v in (x, y, anchors, f64, g64))
        s = _stage(y, g, anchors, eps=eps, q=q, block=bm)
        t = _stage(x, f, anchors, eps=eps, q=q, block=bn)
        rows = np.asarray(_marginal(x, f, s, anchors, eps=eps, q=q, block=bn))
        cols = np.asarray(_marginal(y, g, t, anchors, eps=eps, q=q, block=bm))
    mass = float(rows.sum())
    with np.errstate(all="ignore"):      # a broken answer reads as inf/nan
        shape = float(np.abs(rows / mass - a64).sum())
    dual = float(a64 @ f64 + b64 @ g64)
    cost = float(cost)
    gap = abs(cost - dual) / max(abs(dual), eps)
    return {k: _finite(v) for k, v in dict(
        row_err=shape + gap, col_err=float(np.abs(cols - b64).sum()),
        mass_err=abs(mass - 1.0), row_l1=float(np.abs(rows - a64).sum()),
        cost_gap=gap, dual=dual).items()}


def _finite(v: float) -> float:
    return v if math.isfinite(v) else math.inf


class Result(NamedTuple):
    f: jax.Array
    g: jax.Array
    cost: jax.Array
    n_iter: jax.Array
    marginal_err: jax.Array


@functools.partial(jax.jit, static_argnames=("eps", "q", "tol", "max_iter",
                                             "precision"))
def _solve(x, y, anchors, a, b, *, eps, q, tol, max_iter, precision):
    lx = log_features(x, anchors, eps=eps, q=q, precision=precision)
    ly = log_features(y, anchors, eps=eps, q=q, precision=precision)
    la, lb = jnp.log(a), jnp.log(b)

    def lmat(l_out, l_in, pot):          # log sum_j K_ij e^{pot_j / eps}
        s = logsumexp(l_in + pot[:, None] / eps, axis=0)
        return logsumexp(l_out + s[None, :], axis=1)

    def body(c):
        it, f, g, _ = c
        g = eps * (lb - lmat(ly, lx, f))
        f = eps * (la - lmat(lx, ly, g))
        err = jnp.sum(jnp.abs(jnp.exp(lmat(ly, lx, f) + g / eps) - b))
        return it + 1, f, g, err

    def cond(c):
        it, _, _, err = c
        return (it < max_iter) & (err > tol)

    zero_f = jnp.zeros(x.shape[0], jnp.float32)
    zero_g = jnp.zeros(y.shape[0], jnp.float32)
    it, f, g, err = jax.lax.while_loop(
        cond, body, body((jnp.int32(0), zero_f, zero_g, jnp.float32(jnp.inf))))
    return Result(f, g, jnp.dot(a, f) + jnp.dot(b, g), it, err)


def solve(x, y, anchors, *, eps: float, R: float, tol: float, max_iter: int,
          a=None, b=None, precision=HIGHEST) -> Result:
    """Log-domain Sinkhorn on the reference kernel, features at ``precision``."""
    a = _uniform(x.shape[0]) if a is None else a
    b = _uniform(y.shape[0]) if b is None else b
    q = gaussian_q(R, eps, x.shape[1])
    return _solve(x, y, anchors, a, b, eps=float(eps), q=q, tol=float(tol),
                  max_iter=int(max_iter), precision=precision)
