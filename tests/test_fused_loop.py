"""Megakernel / cadence / mixed-precision coverage.

Contracts under test (the ISSUE-5 acceptance bar):

* the persistent multi-iteration block step (``kernels.fused_loop`` via
  ``GeometryOps.make_block_step``) matches ``inner_steps`` unfused plan
  steps ELEMENTWISE at block boundaries — factored + gaussian, scaling +
  log, with momentum, warm starts and ot_bucket-style zero-weight padding;
* the per-iteration log plan streams the factors four times a trip (two
  LSE contractions, two LSE half-steps) and carries the column
  log-marginal ``log(K^T e^{f/eps})``, which equals the XLA operator's;
* the ``inner_steps`` / ``check_every`` cadence invariance matrix: final
  cost/potentials match the ``check_every=1`` solve to <= 1e-6 rel across
  families and modes, and iteration counts are exact multiples of the
  cadence;
* the bf16 mixed-precision policy stays within documented parity bounds of
  fp32 and actually stores the factors in bfloat16;
* the refusal surfaces: sharded solves reject ``inner_steps``, accelerated
  rejects it too, mis-aligned cadences raise, unknown precisions raise.
"""
import jax
import jax.extend as jex
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BatchedSinkhorn, OTProblem, solve
from repro.core.geometry import (
    ArcCosinePointCloud,
    FactoredPositive,
    GaussianPointCloud,
)
from repro.kernels import fused_loop
from repro.kernels.backend import resolve_backend
from repro.kernels.ops import geometry_ops, log_halfstep

KEY = jax.random.PRNGKey(0)


def _factored(n=96, m=80, r=17, eps=0.5, dead=0):
    xi = jax.random.uniform(KEY, (n, r)) + 0.05
    zt = jax.random.uniform(jax.random.fold_in(KEY, 1), (m, r)) + 0.05
    a = jnp.full((n,), 1.0 / n)
    if dead:
        a = a.at[-dead:].set(0.0)
        a = a / a.sum()
    b = jnp.full((m,), 1.0 / m)
    return FactoredPositive(xi=xi, zeta=zt, eps=eps), a, b


def _gaussian(n=60, m=70, r=33, eps=0.4):
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (n, 2))
    y = jax.random.normal(jax.random.fold_in(KEY, 3), (m, 2)) * 0.7
    anchors = jax.random.normal(jax.random.fold_in(KEY, 4), (r, 2)) * 0.5
    a = jnp.full((n,), 1.0 / n)
    b = jnp.full((m,), 1.0 / m)
    return GaussianPointCloud.build(x, y, anchors, eps=eps), a, b


def _arccos(n=50, m=55, r=21, eps=0.5):
    x = jax.random.normal(jax.random.fold_in(KEY, 5), (n, 2))
    y = jax.random.normal(jax.random.fold_in(KEY, 6), (m, 2)) * 0.8
    anchors = 1.5 * jax.random.normal(jax.random.fold_in(KEY, 7), (r, 2))
    a = jnp.full((n,), 1.0 / n)
    b = jnp.full((m,), 1.0 / m)
    return ArcCosinePointCloud(x, y, anchors, eps=eps), a, b


GEOMS = {"factored": _factored, "gaussian": _gaussian, "arccos": _arccos}


# ---------------------------------------------------------------------------
# Block step == inner_steps unfused plan steps (elementwise at boundaries)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["factored", "gaussian"])
@pytest.mark.parametrize("mode", ["scaling", "log"])
@pytest.mark.parametrize("momentum", [1.0, 1.3])
def test_block_step_matches_unfused(family, mode, momentum):
    geom, a, b = GEOMS[family]()
    # zero-weight atoms on the factored case exercise the masked relax
    if family == "factored":
        geom, a, b = _factored(dead=3)
    plan = geometry_ops(geom, backend="interpret", mode=mode)
    inner = 4
    step, init = plan.make_step(a, b, momentum=momentum)
    block = plan.make_block_step(a, b, inner_steps=inner, momentum=momentum)
    assert block is not None
    bstep, binit = block
    n, m = a.shape[0], b.shape[0]
    if mode == "scaling":
        z0 = (jnp.ones((n,)) * jnp.where(a > 0, 1.0, 0.0), jnp.ones((m,)))
    else:
        z0 = (jnp.where(a > 0, 0.0, -jnp.inf), jnp.zeros((m,)))
    carry = init(*z0)
    for _ in range(inner):
        carry, err = step(carry)
    bcarry, berr = bstep(binit(*z0))
    if mode == "log":
        # the per-iteration step carries the column log-marginal, the
        # megakernel the stage-1 LSE it comes from: finish the block's
        # second stage and compare what the two carry
        zero = jnp.zeros((m, 1))
        lcol = log_halfstep(plan.features[1], bcarry[2], zero, scale=-1.0,
                            backend="interpret")[:, 0]
        bcarry = (bcarry[0], bcarry[1], lcol)
    for ref, got in zip(carry, bcarry):
        finite = jnp.isfinite(ref)
        assert bool(jnp.all(finite == jnp.isfinite(got)))
        np.testing.assert_allclose(
            np.where(np.asarray(finite), np.asarray(ref), 0.0),
            np.where(np.asarray(finite), np.asarray(got), 0.0),
            rtol=2e-6, atol=2e-6,
        )
    # the block-boundary error agrees with the per-iteration error up to
    # f32 reduction-order noise
    np.testing.assert_allclose(float(err), float(berr), rtol=1e-3,
                               atol=1e-7)


def _pallas_calls_by_kernel(fn, *args):
    """Count the ``pallas_call``s in ``fn``'s jaxpr by the jitted kernel
    wrapper (``_log_contract_impl``, ...) that encloses each."""
    counts = {}

    def walk(jaxpr, owner):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                counts[owner] = counts.get(owner, 0) + 1
            for val in eqn.params.values():
                sub = val.jaxpr if isinstance(val, jex.core.ClosedJaxpr) \
                    else val
                if isinstance(sub, jex.core.Jaxpr):
                    walk(sub, eqn.params.get("name", owner))

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return counts


@pytest.mark.parametrize("momentum", [1.0, 1.3])
def test_log_plan_step_streams_factors_four_times(momentum):
    """One per-iteration log step is two LSE contractions and two LSE
    half-steps — the convergence check's half-step IS the next step's
    g-update — and ``init`` is one of each."""
    geom, a, b = _factored(dead=3)
    plan = geometry_ops(geom, backend="interpret", mode="log")
    step, init = plan.make_step(a, b, momentum=momentum)
    f0 = jnp.where(a > 0, 0.0, -jnp.inf)
    g0 = jnp.zeros_like(b)
    assert _pallas_calls_by_kernel(step, init(f0, g0)) == {
        "_log_contract_impl": 2, "_log_halfstep_impl": 2}
    assert _pallas_calls_by_kernel(init, f0, g0) == {
        "_log_contract_impl": 1, "_log_halfstep_impl": 1}


@pytest.mark.parametrize("momentum", [1.0, 1.3])
def test_log_plan_carries_column_log_marginal(momentum):
    """The carried ``lcol`` is the XLA ``log(K^T e^{f/eps})`` of the
    carried ``f`` after every step, and a whole solve through the plan
    matches ``make_log_step``'s XLA solve: dead atoms, momentum."""
    from repro.core.sinkhorn import sinkhorn_log_geometry

    # log-normal factors: a solve of tens of iterations, where uniform
    # ones converge in two
    n, m, r = 96, 80, 17
    xi = jnp.exp(6.0 * jax.random.normal(KEY, (n, r)))
    zt = jnp.exp(6.0 * jax.random.normal(jax.random.fold_in(KEY, 1), (m, r)))
    a = jnp.full((n,), 1.0 / n).at[-3:].set(0.0)
    a = a / a.sum()
    b = jnp.full((m,), 1.0 / m)
    geom = FactoredPositive(xi=xi, zeta=zt, eps=0.5)
    _, log_rmatvec = geom.log_operators()
    plan = geometry_ops(geom, backend="interpret", mode="log")
    step, init = plan.make_step(a, b, momentum=momentum)
    carry = init(jnp.where(a > 0, 0.0, -jnp.inf), jnp.zeros_like(b))
    for _ in range(5):
        carry, _ = step(carry)
        f, _, lcol = carry
        assert lcol.shape == b.shape
        np.testing.assert_allclose(np.asarray(lcol),
                                   np.asarray(log_rmatvec(f)),
                                   rtol=2e-6, atol=2e-6)

    kw = dict(tol=1e-5, max_iter=4000, momentum=momentum)
    res_p = sinkhorn_log_geometry(geom, a, b, use_pallas=True, **kw)
    res_x = sinkhorn_log_geometry(geom, a, b, use_pallas=False, **kw)
    assert int(res_p.n_iter) == int(res_x.n_iter) > 5
    for field in ("f", "g"):
        got, want = getattr(res_p, field), getattr(res_x, field)
        finite = np.isfinite(np.asarray(want))
        assert np.array_equal(finite, np.isfinite(np.asarray(got)))
        np.testing.assert_allclose(np.asarray(got)[finite],
                                   np.asarray(want)[finite],
                                   rtol=2e-4, atol=2e-4, err_msg=field)
    np.testing.assert_allclose(float(res_p.marginal_err),
                               float(res_x.marginal_err), rtol=1e-3,
                               atol=1e-7)


def test_block_step_warm_start_boundary():
    """A SECOND block continues exactly where the first stopped — the
    megakernel carry round-trips through HBM unchanged."""
    geom, a, b = _factored()
    plan = geometry_ops(geom, backend="interpret", mode="scaling")
    step, init = plan.make_step(a, b)
    bstep, binit = plan.make_block_step(a, b, inner_steps=3)
    carry = init(jnp.ones_like(a), jnp.ones_like(b))
    for _ in range(6):
        carry, _ = step(carry)
    bcarry = binit(jnp.ones_like(a), jnp.ones_like(b))
    for _ in range(2):
        bcarry, _ = bstep(bcarry)
    for ref, got in zip(carry, bcarry):
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# Cadence invariance matrix (solve surface)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family,method", [
    ("factored", "factored"),
    ("factored", "log_factored"),
    ("gaussian", "log_factored"),
    ("gaussian", "factored"),
    ("arccos", "log_factored"),
])
@pytest.mark.parametrize("knobs", [
    dict(use_pallas=True, inner_steps=4),
    dict(use_pallas=False, check_every=4),
    dict(use_pallas=False, inner_steps=4),   # degrades to the cadence
])
def test_cadence_invariance(family, method, knobs):
    geom, a, b = GEOMS[family]()
    p = OTProblem.from_geometry(geom, a, b)
    ref = solve(p, method=method, tol=1e-6, use_pallas=False)
    res = solve(p, method=method, tol=1e-6, **knobs)
    assert int(res.n_iter) % 4 == 0
    assert int(res.n_iter) >= int(ref.n_iter)
    assert bool(res.converged)
    rel = abs(float(res.cost - ref.cost)) / max(abs(float(ref.cost)), 1e-12)
    assert rel <= 1e-6, rel
    live = np.asarray(a) > 0
    np.testing.assert_allclose(np.asarray(res.f)[live],
                               np.asarray(ref.f)[live],
                               rtol=1e-4, atol=1e-6)


@pytest.mark.parametrize("method", ["factored", "log_factored"])
def test_cadence_with_momentum_and_warm_start(method):
    geom, a, b = _factored(eps=0.3)
    p = OTProblem.from_geometry(geom, a, b)
    ref = solve(p, method=method, tol=1e-6, momentum=1.4)
    warm = solve(p, method=method, tol=1e-2)
    res = solve(p, method=method, tol=1e-6, momentum=1.4,
                use_pallas=True, inner_steps=2, check_every=4)
    assert int(res.n_iter) % 4 == 0
    rel = abs(float(res.cost - ref.cost)) / abs(float(ref.cost))
    assert rel <= 1e-6, rel
    # warm-started run through the megakernel: the solver entry points
    # accept f_init via the stage machinery — exercise through
    # sinkhorn_log_geometry directly
    from repro.core.sinkhorn import sinkhorn_log_geometry
    res_w = sinkhorn_log_geometry(geom, a, b, tol=1e-6,
                                  f_init=warm.f, g_init=warm.g,
                                  use_pallas=True, inner_steps=4)
    assert int(res_w.n_iter) % 4 == 0
    rel = abs(float(res_w.cost - ref.cost)) / abs(float(ref.cost))
    assert rel <= 1e-6, rel


def test_cadence_with_zero_weight_padding():
    """ot_bucket-style padding: dead atoms with zero weight stay inert
    through the megakernel (scaling AND log), matching the unpadded solve
    elementwise on live atoms."""
    geom, a, b = _factored(n=90, m=90, r=9, eps=0.5)
    n_pad = 128
    xi_p = jnp.concatenate(
        [geom.xi, jnp.broadcast_to(geom.xi[-1:], (n_pad - 90, 9))])
    zt_p = jnp.concatenate(
        [geom.zeta, jnp.broadcast_to(geom.zeta[-1:], (n_pad - 90, 9))])
    a_p = jnp.concatenate([a, jnp.zeros((n_pad - 90,))])
    b_p = jnp.concatenate([b, jnp.zeros((n_pad - 90,))])
    pp = OTProblem.from_features(xi_p, zt_p, a_p, b_p, eps=0.5)
    p = OTProblem.from_geometry(geom, a, b)
    for method in ("factored", "log_factored"):
        ref = solve(p, method=method, tol=1e-6)
        res = solve(pp, method=method, tol=1e-6, use_pallas=True,
                    inner_steps=4)
        pad_ref = solve(pp, method=method, tol=1e-6, use_pallas=False)
        assert bool(res.converged)
        # megakernel == unfused XLA path on the SAME padded problem,
        # elementwise on live atoms (the fused-vs-unfused contract)
        np.testing.assert_allclose(np.asarray(res.f)[:90],
                                   np.asarray(pad_ref.f)[:90],
                                   rtol=1e-4, atol=1e-5)
        # padded vs unpadded agree on the (normalization-free) cost: the
        # scaling path starts dead atoms at u0 = 1 — they pin to 0 after
        # one update, so the transient (and the dual's free constant)
        # differ while the optimum does not; the log path pins f0 = -inf
        # from iteration 0 and matches elementwise too
        rel = abs(float(res.cost - ref.cost)) / abs(float(ref.cost))
        assert rel <= 1e-5, rel
        if method == "factored":
            assert np.all(np.asarray(res.u)[90:] == 0.0)
        else:
            assert np.all(np.asarray(res.f)[90:] == -np.inf)
            np.testing.assert_allclose(np.asarray(res.f)[:90],
                                       np.asarray(ref.f),
                                       rtol=1e-4, atol=1e-5)


def test_annealed_cadence():
    from repro.core import EpsSchedule
    geom, a, b = _gaussian(eps=0.05)
    p = OTProblem.from_geometry(geom, a, b)
    sched = EpsSchedule(eps_init=1.0, decay=0.5)
    ref = solve(p, schedule=sched, tol=1e-5)
    res = solve(p, schedule=sched, tol=1e-5, check_every=4)
    assert bool(res.converged)
    rel = abs(float(res.cost - ref.cost)) / max(abs(float(ref.cost)), 1e-12)
    assert rel <= 1e-5, rel


# ---------------------------------------------------------------------------
# Mixed-precision policy
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family,method", [
    ("factored", "factored"),
    ("factored", "log_factored"),
    ("gaussian", "log_factored"),
])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_bf16_policy_parity(family, method, use_pallas):
    geom, a, b = GEOMS[family]()
    p = OTProblem.from_geometry(geom, a, b)
    ref = solve(p, method=method, tol=1e-5)
    res = solve(p, method=method, tol=1e-5, precision="bf16",
                use_pallas=use_pallas)
    assert bool(res.converged)
    # bf16 stores ~3 significant decimal digits: the fixed point moves by
    # the feature rounding, not by accumulation error (stays f32)
    rel = abs(float(res.cost - ref.cost)) / max(abs(float(ref.cost)), 1e-12)
    assert rel <= 5e-3, rel
    np.testing.assert_allclose(np.asarray(res.f), np.asarray(ref.f),
                               rtol=0.1, atol=5e-2)


def test_bf16_storage_dtype():
    geom, a, b = _factored()
    plan = geometry_ops(geom, backend="interpret", mode="scaling",
                        precision="bf16")
    assert plan.features[0].dtype == jnp.bfloat16
    assert plan.precision == "bf16"
    plan32 = geometry_ops(geom, backend="interpret", mode="scaling")
    assert plan32.features[0].dtype == jnp.float32
    # the XLA operator path stores bf16 too but accumulates/returns f32 —
    # even for a WEAK-typed operand, which dtype promotion alone would
    # silently demote to a bf16 contraction
    mv, _ = geom.operators(precision="bf16")
    out = mv(jnp.ones_like(b))
    assert out.dtype == jnp.float32 and not out.weak_type


def test_bf16_megakernel_block():
    geom, a, b = _factored()
    plan = geometry_ops(geom, backend="interpret", mode="scaling",
                        precision="bf16")
    bstep, binit = plan.make_block_step(a, b, inner_steps=4)
    step, init = plan.make_step(a, b)
    carry = init(jnp.ones_like(a), jnp.ones_like(b))
    for _ in range(4):
        carry, _ = step(carry)
    bcarry, _ = bstep(binit(jnp.ones_like(a), jnp.ones_like(b)))
    np.testing.assert_allclose(np.asarray(carry[0]), np.asarray(bcarry[0]),
                               rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# Budget + refusal surfaces
# ---------------------------------------------------------------------------


def test_vmem_budget_policy():
    # the compiled budget refuses what real VMEM cannot hold; interpret
    # mode (CI/bench) gets headroom
    tpu = resolve_backend("tpu-mosaic")
    interp = resolve_backend("interpret")
    assert fused_loop.block_plan_fits(2048, 2048, 256, 1, jnp.float32,
                                      backend=tpu)
    assert not fused_loop.block_plan_fits(4096, 4096, 256, 1, jnp.float32,
                                          backend=tpu)
    assert fused_loop.block_plan_fits(4096, 4096, 256, 1, jnp.float32,
                                      backend=interp)
    # bf16 halves the factor bytes — shapes near the boundary fit again
    assert fused_loop.block_vmem_bytes(8192, 8192, 128, 1, jnp.bfloat16) \
        < fused_loop.block_vmem_bytes(8192, 8192, 128, 1, jnp.float32)
    # the (n, B) carries are lane-padded: B = 1 costs what B = 128 does
    assert fused_loop.block_vmem_bytes(1024, 1024, 256, 1) \
        == fused_loop.block_vmem_bytes(1024, 1024, 256, 128)


def test_misaligned_cadence_raises():
    geom, a, b = _factored()
    p = OTProblem.from_geometry(geom, a, b)
    with pytest.raises(ValueError, match="multiple of inner_steps"):
        solve(p, method="factored", inner_steps=4, check_every=6,
              use_pallas=True)
    with pytest.raises(ValueError, match="inner_steps must be >= 1"):
        solve(p, method="factored", inner_steps=0)
    with pytest.raises(ValueError, match="unknown precision"):
        solve(p, method="factored", precision="fp8")


def test_accelerated_refuses_block():
    geom, a, b = _factored()
    p = OTProblem.from_geometry(geom, a, b)
    with pytest.raises(ValueError, match="not available"):
        solve(p, method="accelerated", inner_steps=4)
    # check_every alone is supported
    ref = solve(p, method="accelerated", tol=1e-5)
    res = solve(p, method="accelerated", tol=1e-5, check_every=3)
    assert int(res.n_iter) % 3 == 0
    rel = abs(float(res.cost - ref.cost)) / abs(float(ref.cost))
    assert rel <= 1e-5, rel


def test_sharded_refuses_block_honors_cadence():
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    geom, a, b = _factored(n=64, m=64)
    p = OTProblem.from_geometry(geom, a, b)
    with pytest.raises(ValueError, match="megakernel"):
        solve(p, mesh=mesh, inner_steps=4)
    from repro.core import solve_many
    with pytest.raises(ValueError, match="megakernel"):
        solve_many([p], method="factored", mesh=mesh, inner_steps=4)
    ref = solve(p, method="factored", tol=1e-6)
    res = solve(p, mesh=mesh, method="factored", tol=1e-6, check_every=2)
    assert int(res.n_iter) % 2 == 0
    rel = abs(float(res.cost - ref.cost)) / abs(float(ref.cost))
    assert rel <= 1e-6, rel


# ---------------------------------------------------------------------------
# Batched engine: knobs + donated warm starts
# ---------------------------------------------------------------------------


def test_batched_engine_inner_steps():
    geom, a, b = _factored(n=64, m=64, r=8)
    ka = jnp.stack([geom.xi, geom.xi * 1.1])
    kb = jnp.stack([geom.zeta, geom.zeta])
    aw = jnp.stack([a, a])
    bw = jnp.stack([b, b])
    ref = BatchedSinkhorn(eps=0.5, method="factored", tol=1e-6) \
        .solve_stacked(ka, kb, aw, bw)
    eng = BatchedSinkhorn(eps=0.5, method="factored", tol=1e-6,
                          use_pallas=True, inner_steps=2)
    res = eng.solve_stacked(ka, kb, aw, bw)
    assert np.all(np.asarray(res.n_iter) % 2 == 0)
    np.testing.assert_allclose(np.asarray(res.cost), np.asarray(ref.cost),
                               rtol=1e-6)


def test_batched_warm_start_donates():
    geom, a, b = _factored(n=64, m=64, r=8)
    ka = jnp.stack([geom.xi, geom.xi])
    kb = jnp.stack([geom.zeta, geom.zeta])
    aw = jnp.stack([a, a])
    bw = jnp.stack([b, b])
    eng = BatchedSinkhorn(eps=0.5, method="log_factored", tol=1e-6)
    cold = eng.solve_stacked(ka, kb, aw, bw)
    f0, g0 = cold.f, cold.g
    warm = eng.solve_stacked(ka, kb, aw, bw, f_init=f0, g_init=g0)
    np.testing.assert_allclose(np.asarray(warm.cost),
                               np.asarray(cold.cost), rtol=1e-6)
    # a warm start at the fixed point converges in the minimum one check
    assert np.all(np.asarray(warm.n_iter) <= np.asarray(cold.n_iter))
    # the donated buffers are invalidated on backends that support
    # donation; either way the handles must not be silently reused
    with pytest.raises(ValueError, match="donates the pair"):
        eng.solve_stacked(ka, kb, aw, bw, f_init=cold.f)
