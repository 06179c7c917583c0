"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Nothing here runs on a chip: each test lowers a kernel at real widths
for a described ``v5e:2x2`` topology and compiles it with the TPU
compiler installed beside JAX, which refuses what interpret mode cannot
see (misaligned tiles, VMEM over the scoped limit). Every test asserts
that the compiled program holds a Mosaic kernel (``tpu_custom_call``).

The topology is described only inside the module fixture below, never
while a module is imported, and the persistent compilation cache is off
around these compiles (an entry written for a described chip cannot be
read back without one). The tests skip where no v5e can be described.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.shapes import OT_SUPPORT_BUCKETS
from repro.core.geometry import FactoredPositive
from repro.kernels import ops
from repro.kernels.backend import resolve_backend
from repro.kernels.fused_loop import block_plan_fits
from repro.kernels.paged import (
    paged_feature_contract_pallas,
    paged_feature_matvec_pallas,
    paged_halfstep_pallas,
)

N, R, D = 65536, 256, 32          # per-iteration kernel widths
PAGE = 512
TPU = resolve_backend("tpu-mosaic")
DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes) -> str:
    """Lower + compile ``fn`` at ``shapes`` for the described chip; the
    compiled text must contain a Mosaic kernel."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _specs(sharding, *shapes):
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]


# -- per-iteration (streaming) plan ----------------------------------------

_ROW_KERNELS = {
    "feature_contract": (lambda w, v, t: ops.feature_contract(
        w, v, backend=TPU), "v"),
    "feature_matvec": (lambda w, v, t: ops.feature_matvec(
        w, t, backend=TPU), "t"),
    "sinkhorn_halfstep": (lambda w, v, t: ops.sinkhorn_halfstep(
        w, t, v, backend=TPU), "vt"),
    "log_contract": (lambda w, v, t: ops.log_feature_contract(
        w, v, backend=TPU), "v"),
    "log_matvec": (lambda w, v, t: ops.log_matvec(w, t[:, 0], backend=TPU),
                   "t"),
    "log_halfstep": (lambda w, v, t: ops.log_halfstep(
        w, t, v, scale=0.1, backend=TPU), "vt"),
}


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("kernel", sorted(_ROW_KERNELS))
def test_per_iteration_kernel_compiles(one_chip, kernel, dtype):
    fn, uses = _ROW_KERNELS[kernel]
    w, v, t = _specs(one_chip, ((N, R), DTYPES[dtype]), ((N, 1), jnp.float32),
                     ((R, 1), jnp.float32))
    args = [w] + [v if "v" in uses else None] + [t if "t" in uses else None]
    _compile(lambda w_, *rest: fn(w_, *rest), *args)


@pytest.mark.parametrize("log_space", [False, True], ids=["linear", "log"])
def test_gaussian_feature_map_compiles(one_chip, log_space):
    x, anchors, c = _specs(one_chip, ((N, D), jnp.float32),
                           ((R, D), jnp.float32), ((R,), jnp.float32))
    _compile(lambda x_, a_, c_: ops.gaussian_feature_map(
        x_, a_, c_, inv_eps=10.0, log_space=log_space, backend=TPU),
        x, anchors, c)


# -- persistent megakernel --------------------------------------------------


def _largest_admitted(dtype) -> int:
    fits = [b for b in OT_SUPPORT_BUCKETS
            if block_plan_fits(b, b, R, 1, dtype, backend=TPU)]
    assert fits, "the megakernel admits no bucket at r = 256"
    return fits[-1]


def _block_step(mode, precision):
    """One megakernel block through the plan layer: admission included."""
    def run(xi, zeta, a, b):
        plan = ops.geometry_ops(FactoredPositive(xi=xi, zeta=zeta, eps=0.5),
                                mode=mode, precision=precision, backend=TPU)
        built = plan.make_block_step(a, b, inner_steps=8)
        if built is None:
            return None
        step, init = built
        start = (jnp.ones_like(a), jnp.ones_like(b)) if mode == "scaling" \
            else (jnp.zeros_like(a), jnp.zeros_like(b))
        return step(init(*start))
    return run


@pytest.mark.parametrize("batch", [0, 16], ids=["solve", "vmap16"])
@pytest.mark.parametrize("precision", ["highest", "bf16"])
@pytest.mark.parametrize("mode", ["scaling", "log"])
def test_megakernel_compiles_at_largest_admitted_bucket(one_chip, mode,
                                                        precision, batch):
    dtype = jnp.bfloat16 if precision == "bf16" else jnp.float32
    n = _largest_admitted(dtype)
    run = _block_step(mode, precision)

    def shapes(size):
        lead = (batch,) if batch else ()
        return _specs(one_chip, (lead + (size, R), jnp.float32),
                      (lead + (size, R), jnp.float32),
                      (lead + (size,), jnp.float32),
                      (lead + (size,), jnp.float32))

    fn = jax.vmap(run) if batch else run
    text = _compile(fn, *shapes(n))
    assert "fused_loop" in text or "block_kernel" in text
    # the next bucket up is refused by admission, before any compile
    nxt = OT_SUPPORT_BUCKETS[OT_SUPPORT_BUCKETS.index(n) + 1]
    assert not block_plan_fits(nxt, nxt, R, 1, dtype, backend=TPU)
    assert jax.eval_shape(run, *shapes(nxt)[:2],
                          *[jax.ShapeDtypeStruct((nxt,), jnp.float32)] * 2
                          ) is None


# -- paged streaming kernels ------------------------------------------------

_PAGED = {
    "contract": lambda w, v, t, live: paged_feature_contract_pallas(
        w, v, live, page_size=PAGE, backend=TPU),
    "matvec": lambda w, v, t, live: paged_feature_matvec_pallas(
        w, t, live, page_size=PAGE, backend=TPU),
    "halfstep": lambda w, v, t, live: paged_halfstep_pallas(
        w, t, v, live, page_size=PAGE, backend=TPU),
}


@pytest.mark.parametrize("dtype", sorted(DTYPES))
@pytest.mark.parametrize("kernel", sorted(_PAGED))
def test_paged_kernel_compiles(one_chip, kernel, dtype):
    w, v, t, live = _specs(one_chip, ((N, R), DTYPES[dtype]),
                           ((N, 1), jnp.float32), ((R, 1), jnp.float32),
                           ((N // PAGE,), jnp.int32))
    _compile(_PAGED[kernel], w, v, t, live)
