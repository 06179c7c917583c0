"""``gaussian_log_sinkhorn`` with its check spread over the host's cores.

The same plain reference: ``solve``, the control's precision and every
number ``check`` returns are those of
:mod:`bench.references.gaussian_log_sinkhorn`, computed by its own
functions in float64 on the host. Only the schedule differs. The rows go
in chunks of ``CHUNK`` to a pool of threads, one per core (a CPU program
runs on one core and releases Python's lock while it runs), and each chunk
is swept in blocks of ``BLOCK`` rows, whose (BLOCK, r) intermediates stay
in the cores' caches. The chunks' stage-1 LSEs are combined with one
float64 ``logaddexp``, exact up to rounding order.

At n = 2^23 a side and r = 256 one answer is four sweeps of 2^31 kernel
entries: minutes on one core, which the four-chip cell cannot spend per
answer.
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import jax
import numpy as np

from bench.references import gaussian_log_sinkhorn as _plain
from bench.references.gaussian_log_sinkhorn import BF16_3X, gaussian_q, solve

CHUNK = 65536
BLOCK = 2048

__all__ = ["BF16_3X", "check", "solve"]


def _threads() -> int:
    return len(os.sched_getaffinity(0))


def _chunked(pool, fn, p, pot, *rest):
    """``fn`` over the rows of ``p`` and ``pot``, one chunk per task, in
    float64 on the CPU; returns the chunks' results in row order."""
    n = p.shape[0]
    chunk = min(CHUNK, n)
    block = min(BLOCK, chunk)
    if n % chunk or chunk % block:
        raise ValueError(f"size {n} is not a multiple of {chunk} and "
                         f"{chunk} of {block}")
    cpu = jax.devices("cpu")[0]

    def one(i):
        # the float64 switch is per thread
        with jax.enable_x64(True):
            args = [jax.device_put(v, cpu) for v in
                    (p[i:i + chunk], pot[i:i + chunk], *rest)]
            return np.asarray(fn(*args, block=block))

    return list(pool.map(one, range(0, n, chunk)))


def check(x, y, anchors, f, g, cost, *, eps: float, R: float, a=None,
          b=None) -> dict:
    """``gaussian_log_sinkhorn.check``'s readings, on all the host's cores."""
    n, m = x.shape[0], y.shape[0]
    q = gaussian_q(R, eps, x.shape[1])
    x64, y64, u64 = (np.asarray(v, np.float64) for v in (x, y, anchors))
    a64 = np.full(n, 1.0 / n) if a is None else np.asarray(a, np.float64)
    b64 = np.full(m, 1.0 / m) if b is None else np.asarray(b, np.float64)
    f64, g64 = np.asarray(f, np.float64), np.asarray(g, np.float64)
    kw = dict(eps=eps, q=q)

    def stage(p, pot, blk, *, block):
        return _plain._stage(p, pot, blk, block=block, **kw)

    def marginal(p, pot, t, blk, *, block):
        return _plain._marginal(p, pot, t, blk, block=block, **kw)

    # a broken answer reads as inf/nan
    with ThreadPoolExecutor(_threads()) as pool, np.errstate(all="ignore"):
        s = np.logaddexp.reduce(_chunked(pool, stage, y64, g64, u64), axis=0)
        t = np.logaddexp.reduce(_chunked(pool, stage, x64, f64, u64), axis=0)
        rows = np.concatenate(_chunked(pool, marginal, x64, f64, s, u64))
        cols = np.concatenate(_chunked(pool, marginal, y64, g64, t, u64))
        mass = float(rows.sum())
        shape = float(np.abs(rows / mass - a64).sum())
    dual = float(a64 @ f64 + b64 @ g64)
    cost = float(cost)
    gap = abs(cost - dual) / max(abs(dual), eps)
    return {k: _plain._finite(v) for k, v in dict(
        row_err=shape + gap, col_err=float(np.abs(cols - b64).sum()),
        mass_err=abs(mass - 1.0), row_l1=float(np.abs(rows - a64).sum()),
        cost_gap=gap, dual=dual).items()}
