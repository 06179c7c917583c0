"""The Geometry protocol: every cost family's operators vs its dense oracle.

Universal contracts, parametrized over all families (including the padded
bucket shapes of ``configs.shapes.ot_bucket``):

  * ``apply_k`` / ``apply_kt``       match ``dense_kernel()`` matvecs
  * ``log_apply_k`` / ``log_apply_kt`` match ``logsumexp(-C/eps + ./eps)``
    on the geometry's own dense kernel (log-capable families)
  * ``cost_matrix()``                matches the family's dense oracle
  * ``rebuild_at`` / ``anneal_capable`` semantics
  * the Pallas dispatch hook (``kernels.ops.geometry_ops``) reproduces the
    geometry's XLA operators in interpret mode
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.shapes import ot_bucket
from repro.core import (
    ArcCosinePointCloud,
    DenseCost,
    FactoredPositive,
    GaussianPointCloud,
    GridSeparable,
    NystromLowRank,
    OTProblem,
    solve,
    squared_euclidean,
)
from repro.core.features import GaussianFeatureMap

EPS = 0.55
LSE = jax.scipy.special.logsumexp


def _clouds(n, m, d=2, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jnp.clip(jax.random.normal(k1, (n, d)), -2, 2)
    y = jnp.clip(0.7 * jax.random.normal(k2, (m, d)) + 0.2, -2, 2)
    return x, y


def _gaussian_anchors(d=2, r=96, seed=3):
    fm = GaussianFeatureMap(r=r, d=d, eps=EPS, R=3.0)
    return fm.init(jax.random.PRNGKey(seed))


def _make_geometry(family: str, n: int, m: int):
    """Build one geometry of ``family`` with supports of size (n, m)."""
    x, y = _clouds(n, m)
    if family == "dense":
        return DenseCost(squared_euclidean(x, y), EPS)
    if family == "factored":
        U = _gaussian_anchors()
        g = GaussianPointCloud.build(x, y, U, eps=EPS, R=3.0)
        xi, zeta = g.features()
        return FactoredPositive(xi=xi, zeta=zeta, eps=EPS)
    if family == "log_factored":
        U = _gaussian_anchors()
        g = GaussianPointCloud.build(x, y, U, eps=EPS, R=3.0)
        lxi, lzt = g.log_features()
        return FactoredPositive(log_xi=lxi, log_zeta=lzt, eps=EPS)
    if family == "gaussian":
        return GaussianPointCloud.build(x, y, _gaussian_anchors(), eps=EPS,
                                        R=3.0)
    if family == "arccos":
        anchors = 1.5 * jax.random.normal(jax.random.PRNGKey(5), (80, 2))
        return ArcCosinePointCloud(x, y, anchors, eps=EPS, kappa=1e-3)
    if family == "nystrom":
        return NystromLowRank.from_point_clouds(
            x, y, eps=EPS, rank=min(16, n, m), key=jax.random.PRNGKey(7))
    if family == "grid":
        # factor (n, m) into 2-D grids; oracle sizes stay exact
        n1 = max(2, n // 8)
        m1 = max(2, m // 8)
        ax = (jnp.linspace(0.0, 1.0, n1), jnp.linspace(0.0, 1.0, n // n1))
        ay = (jnp.linspace(0.0, 1.2, m1), jnp.linspace(0.0, 1.2, m // m1))
        return GridSeparable.build(ax, ay, eps=EPS)
    raise AssertionError(family)


FAMILIES = ("dense", "factored", "log_factored", "gaussian", "arccos",
            "nystrom", "grid")

# ragged "real" sizes plus the padded power-of-two bucket shapes the
# batched engine actually solves at
SIZES = ((40, 36), (ot_bucket(40), ot_bucket(36)))


# ---------------------------------------------------------------------------
# Universal operator oracles
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("size", SIZES, ids=lambda s: f"n{s[0]}m{s[1]}")
def test_operators_match_dense_kernel(family, size):
    geom = _make_geometry(family, *size)
    n, m = geom.shape
    key = jax.random.PRNGKey(11)
    v = jax.random.uniform(key, (m,)) + 0.1
    u = jax.random.uniform(jax.random.fold_in(key, 1), (n,)) + 0.1
    K = geom.dense_kernel()
    np.testing.assert_allclose(np.asarray(geom.apply_k(v)),
                               np.asarray(K @ v), rtol=3e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(geom.apply_kt(u)),
                               np.asarray(K.T @ u), rtol=3e-4, atol=1e-6)


@pytest.mark.parametrize("family", FAMILIES)
@pytest.mark.parametrize("precision", ["highest", "bf16"])
def test_operators_multiply_in_f32_under_vmap(family, precision):
    """Every contraction of the scaling operators asks for f32 products.
    At its default precision the TPU's MXU rounds f32 operands to bf16;
    the vmapped matvecs of ``solve_many`` and the service then stalled
    far above a 1e-6 marginal tolerance on the chip."""
    geom = _make_geometry(family, 40, 36)
    n, m = geom.shape
    for op, size in zip(geom.operators(precision=precision), (m, n)):
        text = str(jax.make_jaxpr(jax.vmap(op))(jnp.ones((3, size))))
        dots = text.count("dot_general")
        assert dots >= 1
        assert text.count("precision=(Precision.HIGHEST, "
                          "Precision.HIGHEST)") == dots


@pytest.mark.parametrize("family",
                         [f for f in FAMILIES if f != "nystrom"])
@pytest.mark.parametrize("size", SIZES, ids=lambda s: f"n{s[0]}m{s[1]}")
def test_log_operators_match_lse_oracle(family, size):
    """log_apply_k(g) == LSE_j( log K_ij + g_j/eps ) on the geometry's own
    dense kernel — exactly the -C/eps Gibbs form for cost-defined families."""
    geom = _make_geometry(family, *size)
    assert geom.supports_log
    n, m = geom.shape
    key = jax.random.PRNGKey(13)
    g = jax.random.normal(key, (m,)) * 0.3
    f = jax.random.normal(jax.random.fold_in(key, 1), (n,)) * 0.3
    logK = geom.log_dense_kernel()
    np.testing.assert_allclose(
        np.asarray(geom.log_apply_k(g)),
        np.asarray(LSE(logK + (g / EPS)[None, :], axis=1)),
        rtol=2e-4, atol=2e-5,
    )
    np.testing.assert_allclose(
        np.asarray(geom.log_apply_kt(f)),
        np.asarray(LSE(logK + (f / EPS)[:, None], axis=0)),
        rtol=2e-4, atol=2e-5,
    )


@pytest.mark.parametrize("family",
                         [f for f in FAMILIES if f != "nystrom"])
def test_log_operators_match_cost_gibbs(family):
    """The Gibbs form of the same oracle: log_apply_k == LSE(-C/eps + g/eps)
    with C the kernel-consistent (induced) cost -eps * log_dense_kernel().
    For cost-defined families that IS cost_matrix(); Gaussian point clouds
    instead define cost_matrix() as the TRUE sq-Euclidean cost (the Sin
    baseline) and their Monte-Carlo kernel error is pinned separately by
    test_features (Prop 3.1 concentration needs r in the thousands)."""
    geom = _make_geometry(family, 40, 36)
    m = geom.shape[1]
    g = jax.random.normal(jax.random.PRNGKey(17), (m,)) * 0.3
    C_induced = -EPS * geom.log_dense_kernel()
    np.testing.assert_allclose(
        np.asarray(geom.log_apply_k(g)),
        np.asarray(LSE((-C_induced + g[None, :]) / EPS, axis=1)),
        rtol=2e-4, atol=2e-5,
    )
    if family in ("dense", "grid"):
        np.testing.assert_allclose(np.asarray(C_induced),
                                   np.asarray(geom.cost_matrix()),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# cost_matrix family oracles
# ---------------------------------------------------------------------------


def test_dense_cost_matrix_roundtrip():
    x, y = _clouds(30, 25)
    C = squared_euclidean(x, y)
    geom = DenseCost(C, EPS)
    np.testing.assert_allclose(np.asarray(geom.cost_matrix()),
                               np.asarray(C))


def test_gaussian_cost_matrix_is_true_cost():
    x, y = _clouds(30, 25)
    geom = GaussianPointCloud.build(x, y, _gaussian_anchors(), eps=EPS)
    np.testing.assert_allclose(np.asarray(geom.cost_matrix()),
                               np.asarray(squared_euclidean(x, y)),
                               rtol=1e-5, atol=1e-6)


def test_factored_cost_matrix_is_induced():
    geom = _make_geometry("factored", 30, 25)
    xi, zeta = geom.features()
    np.testing.assert_allclose(
        np.asarray(geom.cost_matrix()),
        np.asarray(-EPS * jnp.log(xi @ zeta.T)),
        rtol=1e-4, atol=1e-4,
    )


def test_grid_cost_matrix_is_separable_sum():
    ax = (jnp.linspace(0, 1, 5), jnp.linspace(0, 2, 4))
    ay = (jnp.linspace(0, 1, 3), jnp.linspace(0, 2, 6))
    geom = GridSeparable.build(ax, ay, eps=EPS)
    px = jnp.stack(jnp.meshgrid(*ax, indexing="ij"), -1).reshape(-1, 2)
    py = jnp.stack(jnp.meshgrid(*ay, indexing="ij"), -1).reshape(-1, 2)
    np.testing.assert_allclose(np.asarray(geom.cost_matrix()),
                               np.asarray(squared_euclidean(px, py)),
                               rtol=1e-5, atol=1e-6)


def test_nystrom_refuses_log_and_cost():
    geom = _make_geometry("nystrom", 30, 25)
    with pytest.raises(ValueError, match="log-domain"):
        geom.log_apply_k(jnp.zeros((geom.shape[1],)))
    with pytest.raises(ValueError, match="signed"):
        geom.cost_matrix()


# ---------------------------------------------------------------------------
# rebuild_at / anneal semantics
# ---------------------------------------------------------------------------


def test_rebuild_semantics():
    dense = _make_geometry("dense", 20, 20)
    assert dense.anneal_capable
    assert dense.rebuild_at(0.1).eps == 0.1
    assert dense.rebuild_at(EPS) is dense

    gauss = _make_geometry("gaussian", 20, 20)
    assert gauss.anneal_capable
    g2 = gauss.rebuild_at(0.1)
    assert g2.eps == 0.1 and g2.R == gauss.R

    grid = _make_geometry("grid", 16, 16)
    assert grid.anneal_capable
    assert grid.rebuild_at(0.2).eps == 0.2

    for pinned in ("factored", "log_factored", "arccos", "nystrom"):
        geom = _make_geometry(pinned, 20, 20)
        assert not geom.anneal_capable
        assert geom.rebuild_at(EPS) is geom
        with pytest.raises(ValueError, match="pins the kernel"):
            geom.rebuild_at(EPS / 2)


def test_divergence_subgeometries_are_symmetric():
    for family in ("factored", "log_factored", "gaussian", "arccos", "grid"):
        geom = _make_geometry(family, 24, 20)
        n, m = geom.shape
        assert geom.xx().shape == (n, n)
        assert geom.yy().shape == (m, m)


# ---------------------------------------------------------------------------
# solve() integration: the two new scenarios
# ---------------------------------------------------------------------------


def test_arccos_solve_matches_dense_oracle():
    """Satellite contract: solve(method='arccos') vs the dense log-domain
    solver on the cost induced by the PERTURBED arc-cosine kernel
    k_s + kappa (Lemma 3) — one fixed point, agreement to solver tol."""
    x, y = _clouds(36, 30, seed=21)
    anchors = 1.4 * jax.random.normal(jax.random.PRNGKey(23), (120, 2))
    geom = ArcCosinePointCloud(x, y, anchors, eps=EPS, s=1, sigma=1.4,
                               kappa=5e-3)
    p = OTProblem.from_geometry(geom)
    # tol=1e-6 is the f32 marginal-error floor; tighter just exhausts iters
    res = solve(p, method="arccos", tol=1e-6, max_iter=8000)
    assert bool(res.converged)
    # dense perturbed arc-cosine kernel, straight from the feature product
    xi, zeta = geom.features()
    K_dense = xi @ zeta.T
    assert float(jnp.min(K_dense)) >= 5e-3 - 1e-6      # kappa floor
    oracle = solve(
        OTProblem.from_cost(-EPS * jnp.log(K_dense), eps=EPS),
        method="log_quadratic", tol=1e-6, max_iter=8000,
    )
    np.testing.assert_allclose(float(res.cost), float(oracle.cost),
                               rtol=1e-5)


def test_arccos_reachable_from_gaussian_problem():
    """method='arccos' swaps the cost family on a point-cloud problem."""
    x, y = _clouds(30, 30, seed=25)
    p = OTProblem.from_point_clouds(x, y, _gaussian_anchors(), eps=EPS)
    res = solve(p, method="arccos", rank=64, key=jax.random.PRNGKey(1))
    assert np.isfinite(float(res.cost))
    assert bool(res.converged)


def test_grid_solve_matches_dense():
    ax = (jnp.linspace(0, 1, 8), jnp.linspace(0, 1, 8))
    p = OTProblem.from_grid(ax, eps=0.2)
    res = solve(p, tol=1e-7, max_iter=6000)
    oracle = solve(
        OTProblem.from_cost(p.geometry.cost_matrix(), eps=0.2),
        method="log_quadratic", tol=1e-7, max_iter=6000,
    )
    np.testing.assert_allclose(float(res.cost), float(oracle.cost),
                               rtol=1e-5, atol=1e-7)


def test_nystrom_solve_reports_structured_divergence():
    """Small-eps Nystrom blow-up (paper Figs. 1/3/5) surfaces as
    result.diverged — a structured flag, not unexplained NaNs."""
    x, y = _clouds(60, 60, seed=27)
    p = OTProblem.from_point_clouds(x, y, _gaussian_anchors(), eps=0.02)
    res = solve(p, method="nystrom", rank=12)
    assert not bool(res.converged)
    assert bool(res.diverged)
    # moderate eps: same method, healthy run
    p2 = OTProblem.from_point_clouds(x, y, _gaussian_anchors(), eps=5.0)
    res2 = solve(p2, method="nystrom", rank=48, tol=1e-5)
    assert not bool(res2.diverged)
    assert np.isfinite(float(res2.cost))


def test_nystrom_is_auto_method_for_nystrom_geometry():
    from repro.core.api import _auto_method

    geom = _make_geometry("nystrom", 20, 20)
    assert _auto_method(OTProblem.from_geometry(geom)) == "nystrom"


# ---------------------------------------------------------------------------
# Pallas dispatch hook
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["factored", "gaussian", "arccos"])
def test_geometry_ops_matches_xla_operators(family):
    """The geometry-chosen fused plan reproduces the XLA operators: one
    fused Alg.-1 iteration == the geometry's apply_k/apply_kt math."""
    from repro.kernels.ops import geometry_ops

    geom = _make_geometry(family, 24, 20)
    plan = geometry_ops(geom, backend="interpret")
    assert plan is not None
    xi, zeta = plan.features
    xi_ref, zeta_ref = geom.features()
    np.testing.assert_allclose(np.asarray(xi), np.asarray(xi_ref),
                               rtol=2e-5, atol=1e-6)
    n, m = geom.shape
    a = jnp.full((n, 1), 1.0 / n)
    b = jnp.full((m, 1), 1.0 / m)
    u0 = jnp.ones((n, 1))
    u1, v1 = plan.iteration(a, b, u0)
    # reference iteration through the geometry's XLA operators
    v_ref = (b[:, 0]) / geom.apply_kt(u0[:, 0])
    u_ref = (a[:, 0]) / geom.apply_k(v_ref)
    np.testing.assert_allclose(np.asarray(v1[:, 0]), np.asarray(v_ref),
                               rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(u1[:, 0]), np.asarray(u_ref),
                               rtol=2e-4, atol=1e-6)


def test_geometry_ops_none_for_unfused_families():
    from repro.kernels.ops import geometry_ops

    for mode in ("scaling", "log"):
        assert geometry_ops(_make_geometry("dense", 10, 10),
                            mode=mode) is None
        assert geometry_ops(_make_geometry("nystrom", 10, 10),
                            mode=mode) is None
        assert geometry_ops(_make_geometry("grid", 16, 16),
                            mode=mode) is None


@pytest.mark.parametrize("family", ["factored", "log_factored", "gaussian",
                                    "arccos"])
def test_geometry_ops_log_mode_matches_xla_operators(family):
    """The fused LOG plan reproduces the geometry's exact two-stage LSE:
    one fused log iteration == log_apply_kt / log_apply_k math."""
    from repro.core.geometry import _masked_log
    from repro.kernels.ops import geometry_ops

    geom = _make_geometry(family, 24, 20)
    plan = geometry_ops(geom, backend="interpret", mode="log")
    assert plan is not None and plan.mode == "log"
    lxi, lzt = plan.features
    lxi_ref, lzt_ref = geom.log_features()
    np.testing.assert_allclose(np.asarray(lxi), np.asarray(lxi_ref),
                               rtol=2e-4, atol=2e-4)
    n, m = geom.shape
    a = jnp.full((n,), 1.0 / n)
    b = jnp.full((m,), 1.0 / m)
    f0 = jnp.zeros((n, 1))
    f1, g1 = plan.iteration(_masked_log(a)[:, None], _masked_log(b)[:, None],
                            f0)
    eps = geom.eps
    g_ref = eps * (jnp.log(b) - geom.log_apply_kt(f0[:, 0]))
    f_ref = eps * (jnp.log(a) - geom.log_apply_k(g_ref))
    np.testing.assert_allclose(np.asarray(g1[:, 0]), np.asarray(g_ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(f1[:, 0]), np.asarray(f_ref),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Fused plan on the solver hot path (use_pallas)
# ---------------------------------------------------------------------------


def _bucket_padded_problem(family: str, n: int, m: int):
    """A problem padded to the engine's power-of-two buckets with
    ZERO-WEIGHT atoms (replicated feature rows carry no mass) — the exact
    shape ``BatchedSinkhorn`` solves at, exercising the unguarded divide in
    ``_halfstep_kernel`` against padded rows."""
    geom = _make_geometry(family, n, m)
    n_pad, m_pad = ot_bucket(n), ot_bucket(m)
    a = jnp.concatenate([jnp.full((n,), 1.0 / n), jnp.zeros((n_pad - n,))])
    b = jnp.concatenate([jnp.full((m,), 1.0 / m), jnp.zeros((m_pad - m,))])
    if family == "factored":
        xi, zeta = geom.features()
        pad = lambda w, k: jnp.concatenate(
            [w, jnp.broadcast_to(w[-1:], (k - w.shape[0],) + w.shape[1:])])
        geom = FactoredPositive(xi=pad(xi, n_pad), zeta=pad(zeta, m_pad),
                                eps=geom.eps)
    else:
        assert family == "gaussian"
        pad = lambda p, k: jnp.concatenate(
            [p, jnp.broadcast_to(p[-1:], (k - p.shape[0],) + p.shape[1:])])
        geom = GaussianPointCloud.build(
            pad(geom.x, n_pad), pad(geom.y, m_pad), geom.anchors,
            eps=geom.eps, R=geom.R)
    return geom, a, b


@pytest.mark.parametrize("family", ["factored", "gaussian"])
def test_fused_hot_loop_parity_bucket_padded_zero_weights(family):
    """Acceptance: a factored/Gaussian solve runs THROUGH the fused plan
    (plan-selection hook fires) and matches the XLA operator path
    elementwise at bucket-padded shapes with zero-weight atoms."""
    from repro.core.sinkhorn import sinkhorn_geometry
    from repro.kernels import observe_plan_selection

    geom, a, b = _bucket_padded_problem(family, 40, 36)
    with observe_plan_selection() as events:
        res_p = sinkhorn_geometry(geom, a, b, tol=1e-6, max_iter=4000,
                                  use_pallas=True)
    assert events and events[0]["mode"] == "scaling"
    assert events[0]["geometry"] == type(geom).__name__
    res_x = sinkhorn_geometry(geom, a, b, tol=1e-6, max_iter=4000,
                              use_pallas=False)
    assert int(res_p.n_iter) == int(res_x.n_iter)
    for field in ("u", "v", "f", "g"):
        got, want = getattr(res_p, field), getattr(res_x, field)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=2e-4, atol=1e-6,
            err_msg=f"{family}.{field}")
    np.testing.assert_allclose(float(res_p.cost), float(res_x.cost),
                               rtol=1e-5, atol=1e-7)
    # zero-weight atoms: scalings exactly 0, potentials exactly -inf
    assert np.all(np.asarray(res_p.u[40:]) == 0.0)
    assert np.all(np.isneginf(np.asarray(res_p.f[40:])))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_momentum_with_zero_weight_padded_atoms(use_pallas):
    """Over-relaxation on a bucket-padded problem: padded atoms pin u = 0,
    and 0^{1-w} in the geometric blend used to produce inf * 0 = NaN,
    silently stopping the while_loop after ~2 iterations. The masked relax
    must keep the solve converging on both the XLA and fused paths."""
    from repro.core.sinkhorn import sinkhorn_geometry

    geom, a, b = _bucket_padded_problem("factored", 40, 36)
    res = sinkhorn_geometry(geom, a, b, tol=1e-6, max_iter=4000,
                            momentum=1.3, use_pallas=use_pallas)
    assert bool(res.converged), int(res.n_iter)
    assert np.isfinite(float(res.cost))
    assert np.all(np.asarray(res.u[40:]) == 0.0)
    # same fixed point as the plain solve
    ref = sinkhorn_geometry(geom, a, b, tol=1e-6, max_iter=4000,
                            use_pallas=False)
    np.testing.assert_allclose(float(res.cost), float(ref.cost), rtol=1e-4)


@pytest.mark.parametrize("family", ["log_factored", "gaussian"])
def test_fused_log_hot_loop_parity(family):
    """Log-domain twin: sinkhorn_log_geometry through the fused LSE plan
    elementwise-matches the exact two-stage XLA path, zero weights masked."""
    from repro.core.sinkhorn import sinkhorn_log_geometry
    from repro.kernels import observe_plan_selection

    geom = _make_geometry(family, 28, 24)
    n, m = geom.shape
    a = jnp.full((n,), 1.0 / n).at[-2:].set(0.0)
    a = a / jnp.sum(a)
    b = jnp.full((m,), 1.0 / m)
    with observe_plan_selection() as events:
        res_p = sinkhorn_log_geometry(geom, a, b, tol=1e-6, max_iter=4000,
                                      use_pallas=True)
    assert events and events[0]["mode"] == "log"
    res_x = sinkhorn_log_geometry(geom, a, b, tol=1e-6, max_iter=4000,
                                  use_pallas=False)
    assert int(res_p.n_iter) == int(res_x.n_iter)
    np.testing.assert_allclose(np.asarray(res_p.g), np.asarray(res_x.g),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(float(res_p.cost), float(res_x.cost),
                               rtol=1e-5, atol=1e-6)
    assert np.all(np.isneginf(np.asarray(res_p.f[-2:])))


def test_batched_engine_fused_plan_parity():
    """Acceptance: BatchedSinkhorn.solve_stacked routes every problem in
    the bucket through the fused plan (vmap adds B as a leading Pallas grid
    axis) and matches the XLA engine elementwise."""
    from repro.core import BatchedSinkhorn
    from repro.kernels import observe_plan_selection

    key = jax.random.PRNGKey(9)
    B, n, m, r, eps = 3, 32, 24, 8, 0.5
    xi = jax.random.uniform(key, (B, n, r)) + 0.05
    zt = jax.random.uniform(jax.random.fold_in(key, 1), (B, m, r)) + 0.05
    a = jnp.full((B, n), 1.0 / n)
    b = jnp.full((B, m), 1.0 / m)
    with observe_plan_selection() as events:
        eng_p = BatchedSinkhorn(eps=eps, method="factored", tol=1e-6,
                                max_iter=1000, use_pallas=True)
        res_p = eng_p.solve_stacked(xi, zt, a, b)
    assert events and events[0]["kind"] == "factored"
    eng_x = BatchedSinkhorn(eps=eps, method="factored", tol=1e-6,
                            max_iter=1000, use_pallas=False)
    res_x = eng_x.solve_stacked(xi, zt, a, b)
    np.testing.assert_allclose(np.asarray(res_p.u), np.asarray(res_x.u),
                               rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(res_p.cost),
                               np.asarray(res_x.cost), rtol=1e-5)
    assert np.array_equal(np.asarray(res_p.n_iter), np.asarray(res_x.n_iter))
