"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.features import GaussianFeatureMap
from repro.kernels import (
    feature_contract,
    feature_matvec,
    fused_log_sinkhorn_iteration,
    fused_sinkhorn_iteration,
    gaussian_feature_map,
    log_feature_contract,
    log_halfstep,
    log_matvec,
    sinkhorn_halfstep,
)
from repro.kernels import ref
from repro.kernels.backend import resolve_backend
from repro.kernels.fused_loop import sinkhorn_block_pallas
from repro.kernels.paged import paged_halfstep_pallas
from repro.kernels.tiling import pad_axis, pick_block


@pytest.mark.parametrize("n,r,d", [
    (8, 8, 2), (130, 60, 5), (256, 512, 16), (300, 100, 64), (17, 513, 3),
])
def test_feature_map_shapes(n, r, d):
    key = jax.random.PRNGKey(n + r + d)
    x = jax.random.normal(key, (n, d))
    fm = GaussianFeatureMap(r=r, d=d, eps=0.6, R=3.0)
    U = fm.init(jax.random.fold_in(key, 1))
    logc = (0.25 * d * jnp.log(2 * fm.q)
            + jnp.sum(U * U, -1) / (fm.q * 0.6) - 0.5 * jnp.log(float(r)))
    out = gaussian_feature_map(x, U, logc, inv_eps=1 / 0.6, backend="interpret")
    want = ref.gaussian_feature_map_ref(x, U, logc, inv_eps=1 / 0.6)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("n,r,B", [
    (16, 8, 1), (513, 60, 3), (1024, 512, 4), (100, 1000, 2),
])
def test_feature_contract_shapes(n, r, B):
    key = jax.random.PRNGKey(n * 7 + r)
    xi = jax.random.uniform(key, (n, r)) + 0.05
    u = jax.random.uniform(jax.random.fold_in(key, 1), (n, B)) + 0.05
    out = feature_contract(xi, u, backend="interpret")
    want = ref.feature_contract_ref(xi, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=1e-5)


@pytest.mark.parametrize("m,r,B", [
    (16, 8, 1), (500, 64, 3), (1025, 256, 2),
])
def test_halfstep_shapes(m, r, B):
    key = jax.random.PRNGKey(m + r + B)
    zeta = jax.random.uniform(key, (m, r)) + 0.05
    t = jax.random.uniform(jax.random.fold_in(key, 1), (r, B)) + 0.05
    marg = jax.random.uniform(jax.random.fold_in(key, 2), (m, B)) + 0.5
    out = sinkhorn_halfstep(zeta, t, marg, backend="interpret")
    want = ref.sinkhorn_halfstep_ref(zeta, t, marg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("m,r", [(16, 8), (500, 64), (1023, 300)])
def test_log_matvec_shapes(m, r):
    key = jax.random.PRNGKey(m * 3 + r)
    log_m = jax.random.normal(key, (m, r)) * 3.0
    t = jax.random.normal(jax.random.fold_in(key, 1), (r,)) * 2.0
    out = log_matvec(log_m, t, backend="interpret")
    want = ref.log_matvec_ref(log_m, t)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32])
def test_fused_iteration_converges_like_reference(dtype):
    """Run 50 fused Pallas iterations; marginals must match the jnp loop."""
    key = jax.random.PRNGKey(0)
    n, m, r, B = 64, 48, 32, 2
    xi = (jax.random.uniform(key, (n, r)) + 0.05).astype(dtype)
    zeta = (jax.random.uniform(jax.random.fold_in(key, 1), (m, r)) + 0.05
            ).astype(dtype)
    a = jnp.full((n, B), 1.0 / n, dtype)
    b = jnp.full((m, B), 1.0 / m, dtype)
    u_k = jnp.ones((n, B), dtype)
    u_r = jnp.ones((n, B), dtype)
    v_r = None
    for _ in range(50):
        u_k, v_k = fused_sinkhorn_iteration(xi, zeta, a, b, u_k,
                                            backend="interpret")
        t = xi.T @ u_r
        v_r = b / (zeta @ t)
        u_r = a / (xi @ (zeta.T @ v_r))
    np.testing.assert_allclose(np.asarray(u_k), np.asarray(u_r), rtol=1e-3)
    np.testing.assert_allclose(np.asarray(v_k), np.asarray(v_r), rtol=1e-3)
    # marginal feasibility of the final plan
    col = v_k * (zeta @ (xi.T @ u_k))
    np.testing.assert_allclose(np.asarray(col), np.asarray(b), atol=1e-4)


# ---------------------------------------------------------------------------
# Lane-padding regression sweep: odd r / B (TPU tiles quantize the trailing
# dim to 128 — these shapes exercise the neutral-fill padding of every
# kernel, including the B=1 single-problem solver shape)
# ---------------------------------------------------------------------------


ODD_SHAPES = [(19, 3, 1), (19, 3, 5), (200, 129, 5), (64, 127, 2)]


@pytest.mark.parametrize("n,r,B", ODD_SHAPES)
def test_lane_padding_parity_scaling_kernels(n, r, B):
    key = jax.random.PRNGKey(n * 11 + r + B)
    xi = jax.random.uniform(key, (n, r)) + 0.05
    u = jax.random.uniform(jax.random.fold_in(key, 1), (n, B)) + 0.05
    t = jax.random.uniform(jax.random.fold_in(key, 2), (r, B)) + 0.05
    marg = jax.random.uniform(jax.random.fold_in(key, 3), (n, B)) + 0.5
    np.testing.assert_allclose(
        np.asarray(feature_contract(xi, u, backend="interpret")),
        np.asarray(ref.feature_contract_ref(xi, u)), rtol=2e-4, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(sinkhorn_halfstep(xi, t, marg, backend="interpret")),
        np.asarray(ref.sinkhorn_halfstep_ref(xi, t, marg)),
        rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(feature_matvec(xi, t, backend="interpret")),
        np.asarray(xi @ t), rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("n,r,B", ODD_SHAPES)
def test_lane_padding_parity_log_kernels(n, r, B):
    key = jax.random.PRNGKey(n * 7 + r * 3 + B)
    lw = jax.random.normal(key, (n, r)) * 3.0
    s = jax.random.normal(jax.random.fold_in(key, 1), (n, B)) * 2.0
    t = jax.random.normal(jax.random.fold_in(key, 2), (r, B)) * 2.0
    lmarg = jax.random.normal(jax.random.fold_in(key, 3), (n, B))
    out_c = log_feature_contract(lw, s, backend="interpret")
    np.testing.assert_allclose(
        np.asarray(out_c), np.asarray(ref.log_feature_contract_ref(lw, s)),
        rtol=1e-4, atol=1e-4)
    out_h = log_halfstep(lw, t, lmarg, scale=0.37, backend="interpret")
    np.testing.assert_allclose(
        np.asarray(out_h),
        np.asarray(ref.log_halfstep_ref(lw, t, lmarg, scale=0.37)),
        rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,r", [(19, 3), (64, 127), (33, 129)])
def test_log_matvec_odd_rank_lane_padding(m, r):
    """r is the trailing (lane) dim of log_m — padding fills with -inf, the
    logsumexp identity, so odd ranks match the oracle exactly."""
    key = jax.random.PRNGKey(m + r)
    log_m = jax.random.normal(key, (m, r)) * 3.0
    t = jax.random.normal(jax.random.fold_in(key, 1), (r,)) * 2.0
    np.testing.assert_allclose(
        np.asarray(log_matvec(log_m, t, backend="interpret")),
        np.asarray(ref.log_matvec_ref(log_m, t)), rtol=1e-5, atol=1e-5)


def test_log_kernels_masked_neutral_entries():
    """-inf log-features (zero-weight / padded atoms) are the LSE identity:
    rows carrying them contribute nothing and produce no NaNs."""
    n, r, B = 12, 5, 2
    key = jax.random.PRNGKey(0)
    lw = jax.random.normal(key, (n, r))
    lw = lw.at[3, :].set(-jnp.inf)          # fully masked feature row
    s = jax.random.normal(jax.random.fold_in(key, 1), (n, B))
    s = s.at[5, :].set(-jnp.inf)            # masked potential (zero weight)
    out = log_feature_contract(lw, s, backend="interpret")
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref.log_feature_contract_ref(lw, s)),
        rtol=1e-4, atol=1e-4)
    assert np.all(np.isfinite(np.asarray(out)))


def test_fused_log_iteration_matches_xla_two_stage():
    """One fused log iteration == the exact two-stage LSE update."""
    n, m, r, B, eps = 40, 30, 16, 3, 0.5
    key = jax.random.PRNGKey(2)
    lxi = jax.random.normal(key, (n, r))
    lzt = jax.random.normal(jax.random.fold_in(key, 1), (m, r))
    loga = jnp.log(jnp.full((n, B), 1.0 / n))
    logb = jnp.log(jnp.full((m, B), 1.0 / m))
    f = jax.random.normal(jax.random.fold_in(key, 2), (n, B))
    f_new, g = fused_log_sinkhorn_iteration(
        lxi, lzt, loga, logb, f, eps=eps, backend="interpret")
    lse = jax.scipy.special.logsumexp
    for c in range(B):
        t = lse(lxi + (f[:, c] / eps)[:, None], axis=0)
        g_ref = eps * (logb[:, c] - lse(lzt + t[None, :], axis=1))
        t2 = lse(lzt + (g_ref / eps)[:, None], axis=0)
        f_ref = eps * (loga[:, c] - lse(lxi + t2[None, :], axis=1))
        np.testing.assert_allclose(np.asarray(g[:, c]), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(f_new[:, c]),
                                   np.asarray(f_ref), rtol=1e-4, atol=1e-5)


def test_feature_map_log_space_epilogue():
    """log_space=True skips the exp: output == log of the linear features,
    with padded anchors at exactly -inf upstream (neutral for LSE)."""
    n, r, d = 50, 7, 3
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (n, d))
    fm = GaussianFeatureMap(r=r, d=d, eps=0.7, R=3.0)
    U = fm.init(jax.random.fold_in(key, 1))
    logc = jnp.zeros((r,), jnp.float32)
    lin = gaussian_feature_map(x, U, logc, inv_eps=1 / 0.7, backend="interpret")
    log = gaussian_feature_map(x, U, logc, inv_eps=1 / 0.7, backend="interpret",
                               log_space=True)
    np.testing.assert_allclose(np.asarray(jnp.exp(log)), np.asarray(lin),
                               rtol=2e-4, atol=1e-6)


def test_tiling_helpers():
    assert pick_block(3) == 128
    assert pick_block(129) == 256
    assert pick_block(4096) == 512          # capped
    assert pick_block(200, cap=256) == 256
    arr = jnp.ones((5, 3))
    padded = pad_axis(arr, 1, 128, value=-jnp.inf)
    assert padded.shape == (5, 128)
    assert bool(jnp.all(jnp.isinf(padded[:, 3:])))
    assert pad_axis(arr, 0, 5) is arr       # already aligned: no copy


def test_feature_map_dtype_bf16_inputs():
    """bf16 inputs upcast inside the kernel; output stays f32-accurate."""
    n, r, d = 64, 64, 8
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (n, d)).astype(jnp.bfloat16)
    fm = GaussianFeatureMap(r=r, d=d, eps=1.0, R=3.0)
    U = fm.init(jax.random.fold_in(key, 1)).astype(jnp.bfloat16)
    logc = jnp.zeros((r,), jnp.float32)
    out = gaussian_feature_map(x.astype(jnp.float32),
                               U.astype(jnp.float32), logc,
                               inv_eps=1.0, backend="interpret")
    want = ref.gaussian_feature_map_ref(x.astype(jnp.float32),
                                        U.astype(jnp.float32), logc,
                                        inv_eps=1.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-3,
                               atol=1e-5)


def test_fused_batched_iteration_matches_reference():
    """Per-problem-features batched Pallas iteration (the TPU lowering of
    the BatchedSinkhorn hot loop) vs the plain jnp math, problem by
    problem."""
    from repro.kernels import fused_batched_sinkhorn_iteration

    key = jax.random.PRNGKey(3)
    B, n, m, r = 3, 64, 48, 32
    xi = jax.random.uniform(key, (B, n, r)) + 0.05
    zeta = jax.random.uniform(jax.random.fold_in(key, 1), (B, m, r)) + 0.05
    a = jnp.full((B, n), 1.0 / n)
    b = jnp.full((B, m), 1.0 / m)
    u = jnp.ones((B, n))
    for _ in range(5):
        u, v = fused_batched_sinkhorn_iteration(xi, zeta, a, b, u,
                                                backend="interpret")
    for i in range(B):
        u_r = jnp.ones((n,))
        for _ in range(5):
            v_r = b[i] / (zeta[i] @ (xi[i].T @ u_r))
            u_r = a[i] / (xi[i] @ (zeta[i].T @ v_r))
        np.testing.assert_allclose(np.asarray(u[i]), np.asarray(u_r),
                                   rtol=1e-4)
        np.testing.assert_allclose(np.asarray(v[i]), np.asarray(v_r),
                                   rtol=1e-4)


_W, _V, _T = jnp.ones((128, 16)), jnp.ones((128, 1)), jnp.ones((16, 1))
_CONTRACTING_KERNELS = {
    "feature_contract": lambda: feature_contract(_W, _V, backend="interpret"),
    "feature_matvec": lambda: feature_matvec(_W, _T, backend="interpret"),
    "sinkhorn_halfstep": lambda: sinkhorn_halfstep(_W, _T, _V,
                                                   backend="interpret"),
    "gaussian_feature_map": lambda: gaussian_feature_map(
        jnp.ones((128, 2)), jnp.ones((16, 2)), jnp.zeros((16,)),
        inv_eps=1.0, backend="interpret"),
    "megakernel": lambda: sinkhorn_block_pallas(
        _W, _W, _V, _V, _V, _V, _V, inner_steps=2,
        backend=resolve_backend("interpret")),
    "paged_halfstep": lambda: paged_halfstep_pallas(
        _W, _T, _V, jnp.ones((2,), jnp.int32), page_size=64,
        backend="interpret"),
}


@pytest.mark.parametrize("kernel", sorted(_CONTRACTING_KERNELS))
def test_kernel_contractions_multiply_in_f32(kernel):
    """Every dot in a kernel body asks for f32 products: at the default
    precision Mosaic rounds f32 operands to bf16 (3e-3 relative on a
    v5e), which moved the megakernel's converged costs by 3e-5."""
    text = str(jax.make_jaxpr(_CONTRACTING_KERNELS[kernel])())
    dots = text.count("dot_general")
    assert dots >= 1
    assert text.count("precision=(Precision.HIGHEST, "
                      "Precision.HIGHEST)") == dots
