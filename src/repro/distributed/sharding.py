"""Logical-axis sharding: one place that maps model axes onto mesh axes.

Model code annotates activations with LOGICAL axes ("batch", "seq",
"kvseq", "vocab", ...); the active :class:`MeshContext` turns those into
``with_sharding_constraint`` on the physical mesh. Without an active
context every hint is a no-op, so the same model code runs single-device
smoke tests and 512-chip dry-runs unchanged.

Physical scheme (DESIGN.md §5):
  batch  -> ('pod', 'data')  (or ('data',) single-pod)   — data parallel
  seq    -> 'model'          — context parallelism for train/prefill
  kvseq  -> 'model'          — decode: flash-decoding style KV partition
  vocab  -> 'model'          — column-parallel embedding / LM head
  expert -> 'model'          — expert parallelism (MoE)
  fsdp   -> 'data'           — ZeRO-3 parameter sharding (zero3 archs)
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "MeshContext",
    "use_mesh_context",
    "current_mesh_context",
    "psum_logsumexp",
    "shard",
    "logical_spec",
]


def psum_logsumexp(x: jax.Array, axis_name: str, *, axis: int = 0) -> jax.Array:
    """Distributed logsumexp over a row-sharded array axis.

    Runs INSIDE ``shard_map``: reduces ``x`` over its local ``axis`` AND the
    mesh ``axis_name`` in one exact pass — ``pmax`` of the local maxima,
    shifted local sums, ``psum``, log. The result is replicated over
    ``axis_name`` and the only cross-device traffic is two collectives on
    the reduced shape (for the factored Sinkhorn kernel, one r-vector —
    the paper's whole communication cost).

    ``-inf``-safe: all ``-inf`` slices (the log-features of zero-weight
    padded atoms) shift by 0 instead of ``-inf`` so the result is a clean
    ``-inf`` rather than ``nan`` from ``(-inf) - (-inf)``.

    Partials that are already LSEs over each device's rows (the fused
    stage-1 kernel's output) combine as a one-row slice: ``x = t[None]``.
    """
    local_max = jax.lax.stop_gradient(jnp.max(x, axis=axis))
    # pmax has no differentiation rule — and needs none: the shift cancels
    # out of the exact LSE identity, so stopping its gradient leaves the
    # derivative the ordinary (correct) softmax
    gmax = jax.lax.pmax(local_max, axis_name)
    shift = jax.lax.stop_gradient(jnp.where(jnp.isfinite(gmax), gmax, 0.0))
    local_sum = jnp.sum(jnp.exp(x - jnp.expand_dims(shift, axis)), axis=axis)
    return shift + jnp.log(jax.lax.psum(local_sum, axis_name))


_state = threading.local()


class MeshContext:
    def __init__(self, mesh: Mesh, *, mode: str = "train"):
        self.mesh = mesh
        self.mode = mode                  # train | prefill | decode
        names = mesh.axis_names
        self.dp_axes: Tuple[str, ...] = tuple(
            a for a in ("pod", "data") if a in names
        )
        self.tp_axis: Optional[str] = "model" if "model" in names else None

    def resolve(self, logical: Optional[str]):
        if logical is None:
            return None
        if logical == "batch":
            return self.dp_axes if self.dp_axes else None
        if logical in ("seq", "kvseq", "vocab", "expert", "heads"):
            return self.tp_axis
        if logical == "fsdp":
            return "data" if "data" in self.mesh.axis_names else None
        raise KeyError(f"unknown logical axis {logical!r}")


@contextlib.contextmanager
def use_mesh_context(ctx: Optional[MeshContext]):
    prev = getattr(_state, "ctx", None)
    _state.ctx = ctx
    try:
        yield ctx
    finally:
        _state.ctx = prev


def current_mesh_context() -> Optional[MeshContext]:
    return getattr(_state, "ctx", None)


def logical_spec(*axes: Optional[str]) -> Optional[P]:
    ctx = current_mesh_context()
    if ctx is None:
        return None
    return P(*(ctx.resolve(a) for a in axes))


def shard(x: jax.Array, *axes: Optional[str]) -> jax.Array:
    """Constrain ``x`` to logical ``axes`` (one per dim; None = replicated)."""
    ctx = current_mesh_context()
    if ctx is None:
        return x
    spec = P(*(ctx.resolve(a) for a in axes))
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec)
    )
