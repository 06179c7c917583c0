"""Execution-backend policy for the Pallas kernel package.

Every kernel used to resolve a bare ``interpret: bool`` from
``jax.default_backend() != "tpu"`` — which silently handed a GPU backend
the *interpreted* kernels (orders of magnitude slow). This module replaces
that bool with a first-class :class:`Backend` record, resolved ONCE per
call site from the runtime platform with env/API overrides:

  * ``tpu-mosaic``  — kernels compile through the Mosaic TPU backend;
    sequential grid axes may accumulate into revisited output blocks, and
    the persistent megakernel is admitted up to the VMEM budget.
  * ``gpu-triton``  — kernels compile through Pallas's Triton lowering.
    Grid programs are PARALLEL CTAs: cross-program accumulation into a
    shared output block is a race, so reduction-over-grid kernels must run
    their split-k variants (partials per grid cell + an XLA combine) and
    the fused feature map must cover the d axis in a single block. The
    megakernel admission budget is shared-memory-sized, not VMEM-sized.
  * ``interpret``   — the Python/XLA interpreter (CPU CI, tests). Reached
    only on platforms with no compiled lowering, or by explicit override.

The record carries everything the kernels/plan layer key decisions on:
lane/sublane quanta, the megakernel admission budget, whether grid
reductions need split-k, and the interpret flag. ``resolve_backend()`` is
the single owner of the policy.

Overrides, highest precedence first:

  1. an explicit ``backend=`` record or name at the call site
     (``backend="interpret"`` is the test configuration — the legacy
     ``interpret=`` bool kwarg is gone),
  2. :func:`set_backend` / :func:`backend_scope` (process-level API),
  3. the ``REPRO_BACKEND`` env var (one of the three names above),
  4. ``jax.default_backend()``.
"""
from __future__ import annotations

import contextlib
import os
from typing import NamedTuple, Optional, Union

import jax

from .tiling import LANE, SUBLANE, round_up

__all__ = [
    "Backend",
    "BACKEND_ENV",
    "BACKEND_NAMES",
    "MEGAKERNEL_BUDGET_TPU",
    "MEGAKERNEL_BUDGET_GPU",
    "MEGAKERNEL_BUDGET_INTERPRET",
    "backend_scope",
    "fused_map_admissible",
    "resolve_backend",
    "set_backend",
]

BACKEND_ENV = "REPRO_BACKEND"

# Megakernel (whole-array persistent block) admission budgets, compared
# with ``fused_loop.block_vmem_bytes``. TPU: the kernel's scoped-VMEM limit
# is set to this same number (v5e has 128 MiB of VMEM; operands of an
# unbatched launch sit in VMEM beside the scoped stack, and the count's
# double-buffered inputs and outputs bound them to half the budget). At
# r = 256 it admits n = m = 2048 in f32 and bf16 and refuses 4096. GPU:
# a Triton pallas_call with no grid is one CTA whose whole working set
# must sit in shared memory / registers — 192 KiB covers an H100 SM, which
# the lane-padded count exceeds at every shape, so the GPU lane always
# refuses into the streaming per-iteration plan. Interpret: no real memory
# bound; the cap only guards against accidentally materializing huge
# arrays.
MEGAKERNEL_BUDGET_TPU = 40 * 2**20
MEGAKERNEL_BUDGET_GPU = 192 * 2**10
MEGAKERNEL_BUDGET_INTERPRET = 512 * 2**20


class Backend(NamedTuple):
    """Resolved execution policy threaded through kernels and plans.

    ``name``            — "tpu-mosaic" | "gpu-triton" | "interpret".
    ``platform``        — the ``jax.default_backend()`` string the record
                          was resolved from (informational).
    ``interpret``       — run ``pallas_call`` in interpret mode.
    ``lane``/``sublane``— tile quanta for the trailing / second-to-last
                          dims (the padding contract of ``kernels.tiling``).
    ``block_budget``    — megakernel working-set admission budget in bytes
                          (``fused_loop.block_plan_fits`` reads this).
    ``megakernel``      — whether the persistent whole-array megakernel
                          lowers on this backend at all.
    ``split_reduce``    — grid programs are parallel (Triton CTAs): kernels
                          that reduce ACROSS grid steps must use their
                          split-k variants (per-cell partials + XLA
                          combine) instead of accumulating into a
                          revisited output block.
    ``fused_map_max_d`` — fused Gaussian feature map: largest lane-padded
                          point dimension the single-d-block constraint
                          admits (0 = sequential d grid allowed, no limit).
                          Over the limit, the plan layer refuses into the
                          XLA (streaming) feature map rather than
                          interpreting.
    """

    name: str
    platform: str
    interpret: bool
    lane: int = LANE
    sublane: int = SUBLANE
    block_budget: int = MEGAKERNEL_BUDGET_INTERPRET
    megakernel: bool = True
    split_reduce: bool = False
    fused_map_max_d: int = 0


def _tpu(platform: str = "tpu") -> Backend:
    return Backend(name="tpu-mosaic", platform=platform, interpret=False,
                   lane=LANE, sublane=SUBLANE,
                   block_budget=MEGAKERNEL_BUDGET_TPU,
                   megakernel=True, split_reduce=False, fused_map_max_d=0)


def _gpu(platform: str = "gpu") -> Backend:
    return Backend(name="gpu-triton", platform=platform, interpret=False,
                   lane=LANE, sublane=SUBLANE,
                   block_budget=MEGAKERNEL_BUDGET_GPU,
                   megakernel=True, split_reduce=True, fused_map_max_d=512)


def _interpret(platform: str) -> Backend:
    return Backend(name="interpret", platform=platform, interpret=True,
                   lane=LANE, sublane=SUBLANE,
                   block_budget=MEGAKERNEL_BUDGET_INTERPRET,
                   megakernel=True, split_reduce=False, fused_map_max_d=0)


_BUILDERS = {
    "tpu-mosaic": _tpu,
    "gpu-triton": _gpu,
    "interpret": _interpret,
}
BACKEND_NAMES = tuple(_BUILDERS)

_GPU_PLATFORMS = ("gpu", "cuda", "rocm")

# process-level override installed by set_backend / backend_scope
_OVERRIDE: Optional[Backend] = None


def _from_name(name: str, platform: Optional[str] = None) -> Backend:
    try:
        builder = _BUILDERS[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; expected one of {BACKEND_NAMES}"
        ) from None
    return builder(platform or jax.default_backend())


def _platform_default(platform: str) -> Backend:
    """The compiled-where-possible policy: TPU and GPU backends COMPILE
    their Pallas lowering; only platforms with no lowering interpret."""
    if platform == "tpu":
        return _tpu(platform)
    if platform in _GPU_PLATFORMS:
        return _gpu(platform)
    return _interpret(platform)


def resolve_backend(
    backend: Optional[Union[Backend, str]] = None,
) -> Backend:
    """Resolve the execution backend for a kernel/plan call site.

    Explicit ``backend`` (record or name — ``"interpret"`` is the test
    configuration) wins; otherwise the ambient policy applies
    (:func:`set_backend` override, then ``REPRO_BACKEND``, then
    ``jax.default_backend()``). A GPU platform resolves to ``gpu-triton``
    with ``interpret=False`` — the interpreter is never selected silently
    on a compiled-capable backend.
    """
    if isinstance(backend, Backend):
        return backend
    if backend is not None:
        return _from_name(backend)
    if _OVERRIDE is not None:
        return _OVERRIDE
    env = os.environ.get(BACKEND_ENV)
    if env:
        return _from_name(env)
    return _platform_default(jax.default_backend())


def set_backend(backend: Optional[Union[Backend, str]]) -> Optional[Backend]:
    """Install (or clear, with ``None``) the process-level backend
    override. Returns the previous override so callers can restore it."""
    global _OVERRIDE
    previous = _OVERRIDE
    _OVERRIDE = None if backend is None else resolve_backend(backend)
    return previous


@contextlib.contextmanager
def backend_scope(backend: Union[Backend, str]):
    """``with backend_scope("gpu-triton"): ...`` — scoped override."""
    previous = set_backend(backend)
    try:
        yield resolve_backend()
    finally:
        set_backend(previous)


def fused_map_admissible(d: int, backend: Backend) -> bool:
    """Whether the fused Gaussian feature map lowers on ``backend`` for
    point dimension ``d``. On split-reduce backends (Triton) the d axis
    must ride in ONE block — a sequential accumulation grid would race —
    so lane-padded ``d`` must fit ``fused_map_max_d``; refusals fall back
    to the XLA feature map (see ``kernels.ops.gaussian_feature_map``)."""
    if not backend.split_reduce or backend.fused_map_max_d <= 0:
        return True
    return round_up(d, backend.lane) <= backend.fused_map_max_d
