"""Multi-device tests in a subprocess (8 virtual CPU devices).

The parent test process keeps the single real device; each test spawns
``python -c`` with XLA_FLAGS=--xla_force_host_platform_device_count=8 so
smoke tests/benches elsewhere are unaffected.
"""
import os
import subprocess
import sys
import textwrap


_ENV = {**os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}


def _run(code: str):
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        env=_ENV, capture_output=True, text=True, timeout=600,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


def test_sharded_sinkhorn_matches_single_device():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core import (sinkhorn_factored, sharded_sinkhorn_factored,
                                gaussian_features)
        from repro.core.features import GaussianFeatureMap
        key = jax.random.PRNGKey(0)
        n, m, d, r, eps = 64, 64, 2, 128, 0.7
        x = jax.random.normal(key, (n, d))
        y = jax.random.normal(jax.random.fold_in(key, 1), (m, d)) * 0.5
        fm = GaussianFeatureMap(r=r, d=d, eps=eps, R=3.0)
        U = fm.init(jax.random.fold_in(key, 2))
        xi = gaussian_features(x, U, eps=eps, q=fm.q)
        zt = gaussian_features(y, U, eps=eps, q=fm.q)
        a = jnp.full((n,), 1/n); b = jnp.full((m,), 1/m)
        ref = sinkhorn_factored(xi, zt, a, b, eps=eps, tol=1e-7, max_iter=3000)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        out = sharded_sinkhorn_factored(mesh, xi, zt, a, b, eps=eps,
                                        tol=1e-7, max_iter=3000)
        np.testing.assert_allclose(float(out.cost), float(ref.cost), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(out.u), np.asarray(ref.u), rtol=1e-3)
        print("sharded sinkhorn OK", float(out.cost))
    """)


def test_api_solve_sharded_dispatch():
    """solve(method='sharded') routes through the shard_map solver and
    matches the single-device factored path."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core import OTProblem, solve, gaussian_features
        from repro.core.features import GaussianFeatureMap
        key = jax.random.PRNGKey(0)
        n, m, d, r, eps = 64, 64, 2, 128, 0.7
        x = jax.random.normal(key, (n, d))
        y = jax.random.normal(jax.random.fold_in(key, 1), (m, d)) * 0.5
        fm = GaussianFeatureMap(r=r, d=d, eps=eps, R=3.0)
        U = fm.init(jax.random.fold_in(key, 2))
        xi = gaussian_features(x, U, eps=eps, q=fm.q)
        zt = gaussian_features(y, U, eps=eps, q=fm.q)
        p = OTProblem.from_features(xi, zt, eps=eps)
        ref = solve(p, method="factored", tol=1e-7, max_iter=3000)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        out = solve(p, method="sharded", mesh=mesh, tol=1e-7, max_iter=3000)
        np.testing.assert_allclose(float(out.cost), float(ref.cost), rtol=1e-5)
        print("api sharded dispatch OK", float(out.cost))
    """)


def test_moe_ep_multidevice_matches_dense():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.models.moe import init_moe, moe_dense, moe_ep_local
        key = jax.random.PRNGKey(0)
        T, d, f, E = 128, 16, 32, 8
        p = init_moe(key, d, f, E)
        x = jax.random.normal(jax.random.fold_in(key, 1), (T, d)) * 0.5
        out_d, _ = moe_dense(p, x, top_k=2)
        mesh = Mesh(np.array(jax.devices()).reshape(1, 8), ("data", "model"))
        fn = jax.shard_map(
            lambda p_, x_: moe_ep_local(p_, x_, top_k=2, n_experts=E,
                                        axis="model", capacity_factor=8.0),
            mesh=mesh,
            in_specs=({"router": P(None, None), "up": P("model", None, None),
                       "gate": P("model", None, None),
                       "down": P("model", None, None)}, P("model", None)),
            out_specs=(P("model", None), P()),
            check_vma=False)
        with mesh:
            out_e, _ = fn(p, x)
        np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_d),
                                   rtol=2e-3, atol=2e-4)
        print("EP MoE 8-device OK")
    """)


def test_compressed_psum_close_to_exact():
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim import compressed_psum
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 512)) * 0.1
        fn = jax.shard_map(
            lambda v: (jax.lax.psum(v, "data"),
                       compressed_psum(v, "data")),
            mesh=mesh, in_specs=P("data", None),
            out_specs=(P("data", None), P("data", None)), check_vma=False)
        with mesh:
            exact, comp = fn(x)
        err = float(jnp.max(jnp.abs(exact - comp)))
        scale = float(jnp.max(jnp.abs(exact)))
        assert err < 0.05 * scale + 1e-3, (err, scale)
        print("compressed psum OK", err, scale)
    """)


def test_ssd_context_parallel_8dev_matches_plain():
    """The §Perf mamba2 hillclimb path: CP SSD across 8 'model' ranks must
    be numerically identical to the single-device chunked SSD."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.distributed.sharding import MeshContext, use_mesh_context
        from repro.models.ssm import ssd_chunked, ssd_context_parallel
        key = jax.random.PRNGKey(3)
        B, S, H, P, N = 2, 64, 2, 4, 3
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (B, S, H, P))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
        A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
        Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
        Cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
        y_ref, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=8)
        mesh = Mesh(np.array(jax.devices()).reshape(1, 8),
                    ("data", "model"))
        with mesh, use_mesh_context(MeshContext(mesh)):
            y_cp = ssd_context_parallel(x, dt, A, Bm, Cm, chunk=8)
        np.testing.assert_allclose(np.asarray(y_cp), np.asarray(y_ref),
                                   rtol=2e-3, atol=2e-3)
        print("CP SSD 8-device OK")
    """)


def test_tiny_train_step_on_2x2_mesh():
    """End-to-end sharded train step (pjit + shard_map MoE) on 4 devices."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.configs.shapes import ShapeSpec
        from repro.launch.mesh import make_local_mesh
        from repro.launch.steps import make_train_step
        from repro.models import init_params
        from repro.optim import AdamWConfig, init_adamw
        cfg = get_config("deepseek_v3_671b").tiny(
            param_dtype="float32", compute_dtype="float32",
            d_model=64, n_experts=8, vocab=256, ot_iters=5)
        mesh = make_local_mesh(2, 2)
        shape = ShapeSpec("t", 32, 4, "train")
        step, shapes, shards = make_train_step(cfg, mesh, shape,
                                               AdamWConfig(lr=1e-3))
        key = jax.random.PRNGKey(0)
        params = init_params(key, cfg)
        opt = init_adamw(params, AdamWConfig(lr=1e-3))
        tok = jax.random.randint(key, (4, 32), 0, cfg.vocab)
        batch = {"tokens": tok, "labels": tok}
        with mesh:
            params, opt, metrics = step(params, opt, batch)
        loss = float(metrics["loss"])
        assert np.isfinite(loss), metrics
        print("2x2 sharded MoE train step OK, loss", loss)
    """)
