"""Mixture-of-Experts layer: top-k routing, shared experts, expert parallel.

Two execution paths, numerically equivalent (tested against each other):

* ``dense``  — capacity-free weighted-sum over experts via one einsum.
  Exact and simple; cost scales with E, so it is reserved for smoke tests
  and small-E research runs.

* ``ep``     — production expert parallelism inside ``shard_map``:
  experts are sharded over the 'model' mesh axis; each device's tokens are
  bucketed by destination rank (capacity-bounded), exchanged with a single
  ``all_to_all``, run through the local experts (fori_loop, per-expert
  capacity gather -> FFN -> scatter), and exchanged back. Metadata for the
  return scatter never leaves the source device — the return all_to_all is
  the mirror image of the send, so each source rank un-permutes with its
  own indices. Token drops happen when a capacity bucket overflows
  (capacity_factor config), as in every capacity-based MoE system.

Routing is either classic softmax top-k or the paper-integrated
``sinkhorn`` balanced assignment (repro.core.routing) — the linear-Sinkhorn
solver reused as a router, see DESIGN.md §4.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core.objective import ExecutionPolicy
from ..core.routing import sinkhorn_route
from .layers import trunc_normal

__all__ = ["init_moe", "moe_dense", "moe_ep_local", "router_probs"]


def init_moe(
    key, d_model: int, d_ff: int, n_experts: int, *, dtype=jnp.float32
):
    ks = jax.random.split(key, 4)
    out_std = 0.02 / (2.0 ** 0.5)
    return {
        "router": trunc_normal(ks[0], (d_model, n_experts), std=0.02,
                               dtype=jnp.float32),  # router math stays f32
        "up": trunc_normal(ks[1], (n_experts, d_model, d_ff), std=0.02, dtype=dtype),
        "gate": trunc_normal(ks[2], (n_experts, d_model, d_ff), std=0.02, dtype=dtype),
        "down": trunc_normal(ks[3], (n_experts, d_ff, d_model), std=float(out_std), dtype=dtype),
    }


def router_probs(
    p, x: jax.Array, *, top_k: int, router: str = "softmax",
    sinkhorn_eps: float = 0.05,
    policy: Optional[ExecutionPolicy] = None,
):
    """x (T, d) -> (combine (T, E), aux_loss). combine is zero off top-k.

    ``policy`` is the run-wide OT execution policy (shared with the
    prototype loss); it shapes only the ``sinkhorn`` router's solve.
    """
    logits = (x.astype(jnp.float32) @ p["router"])
    T, E = logits.shape
    if router == "sinkhorn":
        r = sinkhorn_route(logits, top_k=top_k, eps=sinkhorn_eps,
                           policy=policy)
        return r.combine, r.balance_loss
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, top_k)                  # (T, k)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    combine = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], idx
    ].set(gates)
    # Switch-style load balance loss
    load = jnp.mean((combine > 0).astype(jnp.float32), axis=0)
    imp = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(load * imp)
    return combine, aux


def _expert_ffn(w_up, w_gate, w_down, x):
    h = jax.nn.silu(x @ w_gate) * (x @ w_up)
    return h @ w_down


def moe_dense(
    p, x: jax.Array, *, top_k: int, router: str = "softmax",
    policy: Optional[ExecutionPolicy] = None,
) -> tuple[jax.Array, jax.Array]:
    """Exact dense path: every token through every expert, combine-weighted.

    x (T, d) -> (T, d). Cost O(T E d f) — smoke/tests/small-E only.
    """
    combine, aux = router_probs(p, x, top_k=top_k, router=router,
                                policy=policy)
    h = jnp.einsum("td,edf->tef", x, p["gate"].astype(x.dtype))
    u = jnp.einsum("td,edf->tef", x, p["up"].astype(x.dtype))
    y = jax.nn.silu(h) * u
    out = jnp.einsum("tef,efd,te->td", y, p["down"].astype(x.dtype),
                     combine.astype(x.dtype))
    return out, aux


def moe_ep_local(
    p_local,                    # router replicated; up/gate/down LOCAL (E_loc, ...)
    x: jax.Array,               # (T_loc, d) local tokens
    *,
    top_k: int,
    n_experts: int,
    axis: str = "model",
    router: str = "softmax",
    capacity_factor: float = 1.25,
    fsdp_axis: Optional[str] = None,
    policy: Optional[ExecutionPolicy] = None,
) -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE body. MUST run inside shard_map over ``axis``.

    Experts sharded over ``axis``: rank r owns experts [r*E_loc, (r+1)*E_loc).
    With ``fsdp_axis`` set, expert weights arrive additionally sharded over
    that axis on their d/f dim and are all-gathered LAZILY, one expert at a
    time inside the expert loop — live gathered weights drop from
    (E_loc, d, f) x3 to (d, f) x3 (§Perf train-memory hillclimb).
    """
    T, d = x.shape
    n_ranks = jax.lax.axis_size(axis)
    E_loc = n_experts // n_ranks
    combine, aux = router_probs(p_local, x, top_k=top_k, router=router,
                                policy=policy)
    aux = jax.lax.pmean(aux, axis)

    # ---- flatten (token, k) assignments ----
    gates_k, idx_k = jax.lax.top_k(combine, top_k)            # (T, k)
    tok_id = jnp.repeat(jnp.arange(T), top_k)                 # (T*k,)
    exp_id = idx_k.reshape(-1)                                # (T*k,)
    gate = gates_k.reshape(-1)
    dest = exp_id // E_loc                                    # target rank
    e_loc = exp_id % E_loc                                    # local expert there

    # ---- capacity-bounded send buckets ----
    A = T * top_k
    c_send = int(-(-A // n_ranks) * capacity_factor)
    c_send = max(8, ((c_send + 7) // 8) * 8)                  # align
    onehot_dest = jax.nn.one_hot(dest, n_ranks, dtype=jnp.int32)
    pos_in_dest = jnp.cumsum(onehot_dest, axis=0) - onehot_dest
    pos = jnp.sum(pos_in_dest * onehot_dest, axis=1)          # (A,)
    keep = pos < c_send
    slot = jnp.where(keep, dest * c_send + pos, n_ranks * c_send)

    send_x = jnp.zeros((n_ranks * c_send + 1, d), x.dtype).at[slot].set(
        x[tok_id], mode="drop"
    )[:-1]
    send_e = jnp.full((n_ranks * c_send + 1,), E_loc, jnp.int32).at[slot].set(
        e_loc, mode="drop"
    )[:-1]

    # ---- exchange: rows become (source_rank, c_send, ...) ----
    recv_x = jax.lax.all_to_all(
        send_x.reshape(n_ranks, c_send, d), axis, 0, 0, tiled=False
    ).reshape(n_ranks * c_send, d)
    recv_e = jax.lax.all_to_all(
        send_e.reshape(n_ranks, c_send), axis, 0, 0, tiled=False
    ).reshape(n_ranks * c_send)

    # ---- local experts: per-expert capacity gather -> FFN -> scatter ----
    Rn = n_ranks * c_send
    c_exp = int(-(-Rn // max(E_loc, 1)) * capacity_factor)
    c_exp = max(8, ((c_exp + 7) // 8) * 8)
    onehot_e = jax.nn.one_hot(recv_e, E_loc + 1, dtype=jnp.int32)
    pos_e = (jnp.cumsum(onehot_e, axis=0) - onehot_e)
    pos_e = jnp.sum(pos_e * onehot_e, axis=1)                 # (Rn,)
    valid = (recv_e < E_loc) & (pos_e < c_exp)
    out_rows = jnp.zeros((Rn, d), x.dtype)

    def run_expert(out_rows, e):
        sel_slot = jnp.where((recv_e == e) & valid, pos_e, c_exp)
        # gather up to c_exp tokens of expert e
        gather_idx = jnp.full((c_exp + 1,), Rn, jnp.int32).at[sel_slot].set(
            jnp.arange(Rn, dtype=jnp.int32), mode="drop"
        )[:-1]
        xe = jnp.concatenate([recv_x, jnp.zeros((1, d), x.dtype)], 0)[gather_idx]
        wu = jax.lax.dynamic_index_in_dim(p_local["up"], e, 0, False).astype(x.dtype)
        wg = jax.lax.dynamic_index_in_dim(p_local["gate"], e, 0, False).astype(x.dtype)
        wd = jax.lax.dynamic_index_in_dim(p_local["down"], e, 0, False).astype(x.dtype)
        if fsdp_axis is not None:
            # lazy ZeRO-3 gather: only THIS expert's weights materialize
            wu = jax.lax.all_gather(wu, fsdp_axis, axis=0, tiled=True)
            wg = jax.lax.all_gather(wg, fsdp_axis, axis=0, tiled=True)
            wd = jax.lax.all_gather(wd, fsdp_axis, axis=1, tiled=True)
        ye = _expert_ffn(wu, wg, wd, xe)                      # (c_exp, d)
        out_rows = out_rows.at[gather_idx].add(
            jnp.where((gather_idx < Rn)[:, None], ye, 0.0), mode="drop"
        )
        return out_rows, None

    # scan (not fori_loop): reverse-mode differentiable expert loop
    out_rows, _ = jax.lax.scan(
        run_expert, out_rows, jnp.arange(E_loc, dtype=jnp.int32)
    )

    # ---- exchange back (mirror) and un-permute with local metadata ----
    back = jax.lax.all_to_all(
        out_rows.reshape(n_ranks, c_send, d), axis, 0, 0, tiled=False
    ).reshape(n_ranks * c_send, d)
    back = jnp.concatenate([back, jnp.zeros((1, d), x.dtype)], 0)
    contrib = back[jnp.minimum(slot, n_ranks * c_send)]       # (A, d)
    contrib = jnp.where(keep[:, None], contrib, 0.0) * gate[:, None].astype(x.dtype)
    out = jnp.zeros((T, d), x.dtype).at[tok_id].add(contrib)
    return out, aux
