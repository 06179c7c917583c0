"""Mamba2 / SSD (state-space duality) blocks — arXiv:2405.21060.

Training uses the chunked SSD algorithm: the sequence is cut into chunks of
length Q; within a chunk the quadratic "attention-like" form runs on the
MXU, across chunks a linear recurrence on the (H, P, N) chunk states is
scanned. Decode is the O(1) recurrent update on a persistent state — this
is why the ssm/hybrid archs are the ones that RUN the long_500k shape.

Shapes: x (B, S, H*P) with head dim P, state dim N, shared B/C (n_groups=1).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .layers import init_linear, linear, rmsnorm, trunc_normal

__all__ = [
    "init_mamba2",
    "mamba2_train",
    "mamba2_decode",
    "init_mamba2_cache",
    "Mamba2Cache",
]


def _segsum(x: jax.Array) -> jax.Array:
    """(..., Q) -> (..., Q, Q) lower-triangular segment sums:
    out[i, j] = sum_{k=j+1..i} x[k]  (=-inf above diagonal)."""
    Q = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    diff = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((Q, Q), bool), k=0)
    return jnp.where(mask, diff, -jnp.inf)


def ssd_chunked(
    x: jax.Array,        # (B, S, H, P) inputs (already dt-scaled outside? no: raw)
    dt: jax.Array,       # (B, S, H) positive step sizes
    A: jax.Array,        # (H,) negative decay rates
    Bm: jax.Array,       # (B, S, N) input matrix (n_groups=1, shared over heads)
    Cm: jax.Array,       # (B, S, N) output matrix
    *,
    chunk: int = 128,
    init_state: Optional[jax.Array] = None,   # (B, H, P, N)
):
    """Returns (y (B,S,H,P), final_state (B,H,P,N))."""
    Bsz, S, H, P = x.shape
    N = Bm.shape[-1]
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0)))
        Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0)))
    Sp = S + pad
    nC = Sp // chunk
    xb = x.reshape(Bsz, nC, chunk, H, P)
    dtb = dt.reshape(Bsz, nC, chunk, H)
    Bb = Bm.reshape(Bsz, nC, chunk, N)
    Cb = Cm.reshape(Bsz, nC, chunk, N)

    dA = dtb * A[None, None, None, :]                    # (B,C,Q,H) <= 0
    dA = jnp.transpose(dA, (0, 3, 1, 2))                 # (B,H,C,Q)
    dA_cs = jnp.cumsum(dA, axis=-1)                      # within-chunk cumsum

    # ---- intra-chunk (quadratic within chunk, MXU-friendly) ----
    L = jnp.exp(_segsum(dA))                             # (B,H,C,Q,Q)
    scores = jnp.einsum("bcqn,bckn->bcqk", Cb, Bb)       # (B,C,Q,Q) shared/head
    xdt = xb * dtb[..., None]                            # dt-weighted input
    y_diag = jnp.einsum(
        "bcqk,bhcqk,bckhp->bcqhp", scores, L, xdt
    )

    # ---- chunk states ----
    decay_states = jnp.exp(dA_cs[..., -1:] - dA_cs)      # (B,H,C,Q)
    states = jnp.einsum("bckn,bhck,bckhp->bchpn", Bb, decay_states, xdt)

    # ---- inter-chunk linear recurrence on states ----
    chunk_decay = jnp.exp(dA_cs[..., -1])                # (B,H,C)

    def scan_fn(h, inp):
        st, dec = inp                                    # (B,H,P,N), (B,H)
        h_new = h * dec[..., None, None] + st
        return h_new, h

    h0 = (
        jnp.zeros((Bsz, H, P, N), x.dtype)
        if init_state is None
        else init_state.astype(x.dtype)
    )
    states_t = jnp.moveaxis(states, 1, 0)                # (C,B,H,P,N)
    decay_t = jnp.moveaxis(chunk_decay, 2, 0)            # (C,B,H)
    final, prev_states = jax.lax.scan(scan_fn, h0, (states_t, decay_t))
    prev_states = jnp.moveaxis(prev_states, 0, 1)        # (B,C,H,P,N)

    # ---- inter-chunk contribution ----
    state_decay = jnp.exp(dA_cs)                         # (B,H,C,Q)
    y_off = jnp.einsum(
        "bcqn,bhcq,bchpn->bcqhp", Cb, state_decay, prev_states
    )

    y = (y_diag + y_off).reshape(Bsz, Sp, H, P)[:, :S]
    return y, final


# ---------------------------------------------------------------------------
# Full Mamba2 mixer layer
# ---------------------------------------------------------------------------


def ssd_context_parallel(
    x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
    Cm: jax.Array, *, chunk: int = 128,
) -> jax.Array:
    """SSD with the sequence sharded over the 'model' axis (context
    parallelism): each rank runs the chunked SSD on its LOCAL segment with
    zero initial state, ranks exchange one (B, H, P, N) state + one (B, H)
    segment-decay via all_gather, the true inbound state per rank comes
    from an associative linear-recurrence scan over ranks, and a cheap
    linear correction term is added locally.

    This removes the per-chunk resharding traffic of running the global
    chunk scan across a sharded axis (§Perf, mamba2 collective hillclimb).
    Falls back to plain ssd_chunked off-mesh.
    """
    from ..distributed.sharding import current_mesh_context

    ctx = current_mesh_context()
    if ctx is None or ctx.tp_axis is None:
        y, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
        return y
    tp = ctx.tp_axis
    dp = ctx.dp_axes if ctx.dp_axes else None
    from jax.sharding import PartitionSpec as P

    def body(x_l, dt_l, B_l, C_l):
        y0, h_loc = ssd_chunked(x_l, dt_l, A, B_l, C_l, chunk=chunk)
        dA = dt_l * A[None, None, :]                       # (B, S_l, H)
        dacs = jnp.cumsum(dA, axis=1)                      # within segment
        seg_decay = jnp.exp(dacs[:, -1, :])                # (B, H)
        hs = jax.lax.all_gather(h_loc, tp)                 # (R, B,H,P,N)
        ds = jax.lax.all_gather(seg_decay, tp)             # (R, B,H)

        def combine(a, b):
            d1, h1 = a
            d2, h2 = b
            return d1 * d2, h1 * d2[..., None, None] + h2

        D, H = jax.lax.associative_scan(combine, (ds, hs), axis=0)
        r = jax.lax.axis_index(tp)
        # inbound state = cumulative state after ranks 0..r-1 (zero for r=0)
        Hpad = jnp.concatenate([jnp.zeros_like(H[:1]), H], axis=0)
        init = jax.lax.dynamic_index_in_dim(Hpad, r, 0, keepdims=False)
        y_corr = jnp.einsum(
            "bsn,bhs,bhpn->bshp", C_l,
            jnp.exp(jnp.moveaxis(dacs, 1, 2)), init.astype(x_l.dtype))
        return y0 + y_corr.astype(y0.dtype)

    fn = jax.shard_map(
        body, mesh=ctx.mesh,
        in_specs=(P(dp, tp, None, None), P(dp, tp, None),
                  P(dp, tp, None), P(dp, tp, None)),
        out_specs=P(dp, tp, None, None),
        check_vma=False,
    )
    return fn(x, dt, Bm, Cm)


def init_mamba2(
    key, d_model: int, *, d_state: int = 128, head_dim: int = 64,
    expand: int = 2, conv_kernel: int = 4, dtype=jnp.float32,
):
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    ks = jax.random.split(key, 4)
    conv_dim = d_inner + 2 * d_state
    out_std = 0.02 / (2.0 ** 0.5)
    return {
        # order: [z (gate), x, B, C, dt]
        "in_proj": init_linear(
            ks[0], d_model, 2 * d_inner + 2 * d_state + n_heads, dtype=dtype
        ),
        "conv_w": trunc_normal(ks[1], (conv_kernel, conv_dim), std=0.1, dtype=dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(
            jnp.linspace(1.0, 16.0, n_heads, dtype=jnp.float32)
        ).astype(dtype),
        "dt_bias": jnp.zeros((n_heads,), dtype),
        "D": jnp.ones((n_heads,), dtype),
        "norm_w": jnp.ones((d_inner,), dtype),
        "out_proj": init_linear(ks[2], d_inner, d_model, std=out_std, dtype=dtype),
    }


def _causal_conv(x: jax.Array, w: jax.Array, b: jax.Array) -> jax.Array:
    """Depthwise causal conv over seq: x (B,S,C), w (K,C)."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(
        xp[:, k : k + x.shape[1], :] * w[k][None, None, :] for k in range(K)
    )
    return jax.nn.silu(out + b[None, None, :])


def _split_proj(zxbcdt, d_inner, d_state, n_heads):
    z = zxbcdt[..., :d_inner]
    xr = zxbcdt[..., d_inner : 2 * d_inner]
    Bm = zxbcdt[..., 2 * d_inner : 2 * d_inner + d_state]
    Cm = zxbcdt[..., 2 * d_inner + d_state : 2 * d_inner + 2 * d_state]
    dt = zxbcdt[..., 2 * d_inner + 2 * d_state :]
    return z, xr, Bm, Cm, dt


def mamba2_train(
    p, x: jax.Array, *, d_state: int = 128, head_dim: int = 64,
    expand: int = 2, chunk: int = 128,
) -> jax.Array:
    B, S, d_model = x.shape
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    zxbcdt = linear(p["in_proj"], x)
    z, xr, Bm, Cm, dt = _split_proj(zxbcdt, d_inner, d_state, n_heads)
    conv_in = jnp.concatenate([xr, Bm, Cm], axis=-1)
    conv_out = _causal_conv(conv_in, p["conv_w"], p["conv_b"])
    xr = conv_out[..., :d_inner]
    Bm = conv_out[..., d_inner : d_inner + d_state]
    Cm = conv_out[..., d_inner + d_state :]
    dt = jax.nn.softplus(dt + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    xh = xr.reshape(B, S, n_heads, head_dim)
    # context-parallel on a mesh (seq sharded over 'model'), plain otherwise
    y = ssd_context_parallel(xh, dt, A, Bm, Cm, chunk=chunk)
    y = y + p["D"][None, None, :, None] * xh
    y = y.reshape(B, S, d_inner)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_w"])
    return linear(p["out_proj"], y)


class Mamba2Cache(NamedTuple):
    conv: jax.Array       # (B, K-1, conv_dim) rolling conv inputs
    state: jax.Array      # (B, H, P, N) SSM state
    length: jax.Array


def init_mamba2_cache(
    batch, d_model, *, d_state=128, head_dim=64, expand=2, conv_kernel=4,
    dtype=jnp.float32,
):
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    conv_dim = d_inner + 2 * d_state
    return Mamba2Cache(
        jnp.zeros((batch, conv_kernel - 1, conv_dim), dtype),
        jnp.zeros((batch, n_heads, head_dim, d_state), dtype),
        jnp.zeros((), jnp.int32),
    )


def mamba2_decode(
    p, x: jax.Array, cache: Mamba2Cache, *, d_state: int = 128,
    head_dim: int = 64, expand: int = 2,
):
    """O(1) single-token state update. x (B, 1, d_model)."""
    B = x.shape[0]
    d_model = x.shape[-1]
    d_inner = expand * d_model
    n_heads = d_inner // head_dim
    zxbcdt = linear(p["in_proj"], x)
    z, xr, Bm, Cm, dt = _split_proj(zxbcdt, d_inner, d_state, n_heads)
    conv_in = jnp.concatenate([xr, Bm, Cm], axis=-1)[:, 0]   # (B, conv_dim)
    hist = jnp.concatenate([cache.conv, conv_in[:, None, :]], axis=1)
    w = p["conv_w"]
    conv_out = jnp.einsum("bkc,kc->bc", hist, w) + p["conv_b"][None, :]
    conv_out = jax.nn.silu(conv_out)
    xr = conv_out[:, :d_inner]
    Bv = conv_out[:, d_inner : d_inner + d_state]
    Cv = conv_out[:, d_inner + d_state :]
    dtv = jax.nn.softplus(dt[:, 0] + p["dt_bias"][None, :])   # (B, H)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    decay = jnp.exp(dtv * A[None, :])                         # (B, H)
    xh = xr.reshape(B, n_heads, head_dim)
    upd = jnp.einsum("bhp,bn,bh->bhpn", xh, Bv, dtv)
    state = cache.state * decay[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", state, Cv)
    y = y + p["D"][None, :, None] * xh
    y = y.reshape(B, 1, d_inner)
    y = rmsnorm(y * jax.nn.silu(z), p["norm_w"])
    out = linear(p["out_proj"], y)
    new_cache = Mamba2Cache(hist[:, 1:], state.astype(cache.state.dtype),
                            cache.length + 1)
    return out, new_cache
