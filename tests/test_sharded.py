"""Sharded-vs-single-device parity matrix on 8 virtual CPU devices.

Each test spawns ``python -c`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the parent keeps
the single real device — see conftest note). CI's ``multi-device`` job
runs this module plus ``test_distributed.py`` on every PR so the SPMD
code paths are exercised without real meshes.

Covers the tentpole contracts:
  * log-domain sharded solver == ``sinkhorn_log_geometry`` to <= 1e-6 rel
    (iterates AND cost) at eps = 0.01, where the scaling-space sharded
    path over/underflows — the acceptance criterion;
  * the scaling/log x factored/gaussian/arccos parity matrix, with
    warm-started second solves and uneven ``n % p != 0`` supports;
  * pad-safety at ``ot_bucket``-padded shapes with zero-weight rows
    landing on >= 2 shards (regression: the old ``_sharded_body``
    initialized u0 = v0 = ones and never masked zero-weight atoms);
  * ``rot_geometry``'s envelope VJP under ``shard_map`` (psum'd dual
    value replicated; feature gradients match single-device);
  * the sharded Sinkhorn divergence and its gradients, including the
    REPLICATED shared anchors;
  * ``solve(mesh=)`` auto-dispatch and ``solve_many(mesh=)``.
"""
import os
import subprocess
import sys
import textwrap

_ENV = {**os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "PYTHONPATH": os.path.join(os.path.dirname(__file__), "..", "src")}

_PRELUDE = """
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.core import (
        ArcCosinePointCloud, FactoredPositive, GaussianPointCloud,
        OTProblem, sharded_sinkhorn_geometry, sinkhorn_geometry,
        sinkhorn_log_geometry, solve, solve_many,
    )
    key = jax.random.PRNGKey(0)
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))

    def clouds(n, m, d=2, scale=0.5):
        x = jax.random.normal(key, (n, d)) * scale
        y = jax.random.normal(jax.random.fold_in(key, 1), (m, d)) * scale
        return x, y

    def uniform(n, m):
        return jnp.full((n,), 1.0 / n), jnp.full((m,), 1.0 / m)
"""


def _run(code: str):
    res = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(_PRELUDE + code)],
        env=_ENV, capture_output=True, text=True, timeout=600,
    )
    assert res.returncode == 0, f"STDOUT:\n{res.stdout}\nSTDERR:\n{res.stderr}"
    return res.stdout


def test_log_sharded_matches_single_device_at_small_eps():
    """ACCEPTANCE: at eps = 0.01 the log-domain sharded solver matches
    ``sinkhorn_log_geometry`` iterates and cost to <= 1e-6 rel on 8
    devices — the regime where the scaling-space sharded path is not even
    runnable (exp(-C/eps) under/overflows)."""
    _run("""
        eps = 0.01
        n, m, r = 96, 80, 64
        x, y = clouds(n, m)
        anchors = jax.random.normal(jax.random.fold_in(key, 2), (r, 2)) * 0.5
        a, b = uniform(n, m)
        geom = GaussianPointCloud.build(x, y, anchors, eps=eps, R=2.0)
        # fixed iteration count -> raw trajectory comparison
        ref = sinkhorn_log_geometry(geom, a, b, tol=0.0, max_iter=250)
        out = sharded_sinkhorn_geometry(mesh, geom, a, b, mode="log",
                                        tol=0.0, max_iter=250)
        scale_f = float(jnp.max(jnp.abs(ref.f)))
        df = float(jnp.max(jnp.abs(out.f - ref.f))) / scale_f
        dg = float(jnp.max(jnp.abs(out.g - ref.g))) / scale_f
        dc = abs(float(out.cost - ref.cost)) / abs(float(ref.cost))
        assert df <= 1e-6 and dg <= 1e-6, (df, dg)
        assert dc <= 1e-6, dc
        # and the scaling-space path really is out of reach at this eps:
        # the Gibbs kernel entries underflow f32, poisoning the scalings
        sc = sharded_sinkhorn_geometry(mesh, geom, a, b, mode="scaling",
                                       tol=1e-6, max_iter=50)
        assert bool(sc.diverged) or not bool(sc.converged)
        print("small-eps log parity OK", df, dg, dc)
    """)


def test_parity_matrix_families_modes_warm_uneven():
    """scaling AND log x factored/gaussian/arccos, warm-started second
    solve, uneven n % 8 != 0 supports — all vs the single-device
    geometry solvers, elementwise on fixed-iteration trajectories."""
    _run("""
        eps = 0.2
        for n, m in ((64, 56), (91, 77)):          # even and uneven shards
            x, y = clouds(n, m)
            anchors = jax.random.normal(
                jax.random.fold_in(key, 2), (32, 2)) * 0.5
            a, b = uniform(n, m)
            xi = jax.random.uniform(key, (n, 24)) + 0.05
            zt = jax.random.uniform(jax.random.fold_in(key, 3), (m, 24)) + 0.05
            fams = dict(
                factored=FactoredPositive(xi=xi, zeta=zt, eps=eps),
                gaussian=GaussianPointCloud.build(x, y, anchors, eps=eps,
                                                  R=2.0),
                arccos=ArcCosinePointCloud(x, y, anchors, eps=eps),
            )
            for fam, geom in fams.items():
                for mode in ("scaling", "log"):
                    runner = (sinkhorn_geometry if mode == "scaling"
                              else sinkhorn_log_geometry)
                    ref = runner(geom, a, b, tol=0.0, max_iter=40)
                    out = sharded_sinkhorn_geometry(
                        mesh, geom, a, b, mode=mode, tol=0.0, max_iter=40)
                    np.testing.assert_allclose(
                        np.asarray(out.g), np.asarray(ref.g),
                        rtol=2e-5, atol=2e-6,
                        err_msg=f"{fam}/{mode}/n{n}")
                    np.testing.assert_allclose(
                        float(out.cost), float(ref.cost), rtol=1e-5,
                        err_msg=f"{fam}/{mode}/n{n}")
                # warm-started second solve (log): must match the
                # single-device warm start AND take fewer iters than cold
                cold = sharded_sinkhorn_geometry(
                    mesh, geom, a, b, mode="log", tol=1e-5, max_iter=2000)
                warm = sharded_sinkhorn_geometry(
                    mesh, geom, a, b, mode="log", tol=1e-5, max_iter=2000,
                    f_init=cold.f, g_init=cold.g)
                ref_warm = sinkhorn_log_geometry(
                    geom, a, b, tol=1e-5, max_iter=2000,
                    f_init=cold.f, g_init=cold.g)
                assert int(warm.n_iter) <= int(cold.n_iter), fam
                np.testing.assert_allclose(
                    float(warm.cost), float(ref_warm.cost), rtol=1e-5,
                    err_msg=f"warm/{fam}/n{n}")
                print("parity OK", fam, n, m)
    """)


def test_pad_safety_zero_weight_rows_across_shards():
    """Regression: zero-weight atoms at ot_bucket-padded shapes, with the
    zero rows landing on >= 2 different shards. The old ``_sharded_body``
    initialized u0 = v0 = ones with no masking; the padded solve must
    match the single-device masked solve elementwise and keep u = 0 /
    f = -inf on every zero-weight atom."""
    _run("""
        from repro.configs.shapes import ot_bucket
        eps = 0.3
        n_live, m_live = 50, 44
        n, m = ot_bucket(n_live), ot_bucket(m_live)       # 64, 64
        assert n % 8 == 0
        xi = jax.random.uniform(key, (n, 16)) + 0.05
        zt = jax.random.uniform(jax.random.fold_in(key, 3), (m, 16)) + 0.05
        # zero weights: the padded tail (shards 7, 8) plus a few interior
        # rows on shard 1 -> zero-weight atoms on >= 3 different shards
        a = jnp.full((n,), 0.0).at[:n_live].set(1.0 / (n_live - 2))
        a = a.at[jnp.array([3, 5])].set(0.0)
        b = jnp.full((m,), 0.0).at[:m_live].set(1.0 / m_live)
        geom = FactoredPositive(xi=xi, zeta=zt, eps=eps)
        for mode, runner in (("scaling", sinkhorn_geometry),
                             ("log", sinkhorn_log_geometry)):
            ref = runner(geom, a, b, tol=1e-6, max_iter=2000)
            out = sharded_sinkhorn_geometry(mesh, geom, a, b, mode=mode,
                                            tol=1e-6, max_iter=2000)
            assert np.isfinite(float(out.cost)), mode
            np.testing.assert_allclose(float(out.cost), float(ref.cost),
                                       rtol=1e-5, err_msg=mode)
            np.testing.assert_allclose(np.asarray(out.u), np.asarray(ref.u),
                                       rtol=2e-4, atol=1e-7, err_msg=mode)
            u = np.asarray(out.u); f = np.asarray(out.f)
            dead = np.asarray(a) == 0
            assert np.all(u[dead] == 0.0), mode
            assert np.all(np.isneginf(f[dead])), mode
            print("pad safety OK", mode, float(out.cost))
    """)


def test_rot_geometry_envelope_vjp_under_shard_map():
    """The generic envelope VJP runs INSIDE shard_map unchanged: the
    psum'd dual value is replicated, and the log-feature gradients match
    the single-device rule (psum's transpose routes every shard's
    contribution into the cotangents)."""
    _run("""
        from jax.sharding import PartitionSpec as P
        from repro.core import rot_geometry
        from repro.core.sharded import RowShardedFactored
        eps, n, m, r = 0.1, 48, 40, 32
        a, b = uniform(n, m)
        lxi = jnp.log(jax.random.uniform(key, (n, r)) + 0.05)
        lzt = jnp.log(jax.random.uniform(jax.random.fold_in(key, 5),
                                         (m, r)) + 0.05)

        def rot_ref(lx, lz):
            return rot_geometry(
                FactoredPositive(log_xi=lx, log_zeta=lz, eps=eps),
                a, b, 1e-6, 2000)

        def rot_sh(lx, lz):
            def body(lx_, lz_, a_, b_):
                g = RowShardedFactored(log_xi=lx_, log_zeta=lz_, eps=eps,
                                       axis="data")
                return rot_geometry(g, a_, b_, 1e-6, 2000)
            fn = jax.shard_map(
                body, mesh=mesh,
                in_specs=(P("data", None), P("data", None),
                          P("data"), P("data")),
                out_specs=P(), check_vma=False)
            return fn(lx, lz, a, b)

        v1, g1 = jax.value_and_grad(rot_ref, argnums=(0, 1))(lxi, lzt)
        v2, g2 = jax.value_and_grad(rot_sh, argnums=(0, 1))(lxi, lzt)
        np.testing.assert_allclose(float(v2), float(v1), rtol=1e-6)
        for name, gr, gs in zip(("log_xi", "log_zeta"), g1, g2):
            np.testing.assert_allclose(np.asarray(gs), np.asarray(gr),
                                       rtol=1e-4, atol=1e-9, err_msg=name)
        print("sharded rot_geometry OK", float(v2))
    """)


def test_sharded_divergence_value_and_gradients():
    """``sinkhorn_divergence_geometry(mesh=...)``: value and gradients —
    including the REPLICATED shared anchors (the GAN theta) — match the
    single-device divergence."""
    _run("""
        from repro.core import sinkhorn_divergence_geometry
        eps, r = 0.1, 32
        anchors = jax.random.normal(jax.random.fold_in(key, 2), (r, 2)) * 0.5
        for n, m in ((48, 40), (53, 41)):      # even and uneven shards
            x, y = clouds(n, m)

            def div(x_, y_, anc, mesh_=None):
                g = GaussianPointCloud.build(x_, y_, anc, eps=eps, R=2.0)
                return sinkhorn_divergence_geometry(
                    g, tol=1e-6, max_iter=2000, mesh=mesh_)

            v1, g1 = jax.value_and_grad(div, argnums=(0, 1, 2))(x, y, anchors)
            v2, g2 = jax.value_and_grad(
                lambda x_, y_, anc: div(x_, y_, anc, mesh))(x, y, anchors)
            np.testing.assert_allclose(float(v2), float(v1), rtol=1e-5,
                                       atol=1e-7)
            for name, gr, gs in zip(("x", "y", "anchors"), g1, g2):
                np.testing.assert_allclose(np.asarray(gs), np.asarray(gr),
                                           rtol=1e-3, atol=1e-6, err_msg=name)
            # uneven pads are exactly inert from iteration 0 (masked
            # _log_init): the fixed-iteration transient matches too
            t1 = div(x, y, anchors)
            t2 = div(x, y, anchors, mesh)
            np.testing.assert_allclose(float(t2), float(t1), rtol=1e-6)
            print("sharded divergence OK", n, m, float(v2))
    """)


def test_solve_mesh_auto_dispatch_and_solve_many():
    """``solve(mesh=)`` auto-selects the sharded twin of the local auto
    table (log for point clouds, scaling for linear factors) and
    ``solve_many(mesh=)`` routes every problem through the mesh."""
    _run("""
        from repro.core.api import _auto_method
        eps, n, m = 0.1, 64, 56
        x, y = clouds(n, m)
        anchors = jax.random.normal(jax.random.fold_in(key, 2), (32, 2)) * 0.5
        cloud_p = OTProblem.from_point_clouds(x, y, anchors, eps=eps, R=2.0)
        xi = jax.random.uniform(key, (n, 24)) + 0.05
        zt = jax.random.uniform(jax.random.fold_in(key, 3), (m, 24)) + 0.05
        feat_p = OTProblem.from_features(xi, zt, eps=0.5)
        assert _auto_method(cloud_p, mesh) == "sharded_log"
        assert _auto_method(feat_p, mesh) == "sharded"
        for p, meth in ((cloud_p, "log_factored"), (feat_p, "factored")):
            ref = solve(p, method=meth, tol=1e-6, max_iter=2000)
            out = solve(p, mesh=mesh, tol=1e-6, max_iter=2000)
            np.testing.assert_allclose(float(out.cost), float(ref.cost),
                                       rtol=1e-5)
        outs = solve_many([cloud_p, cloud_p], method="log_factored",
                          mesh=mesh, tol=1e-6, max_iter=2000)
        refc = float(solve(cloud_p, method="log_factored", tol=1e-6,
                           max_iter=2000).cost)
        for o in outs:
            np.testing.assert_allclose(float(o.cost), refc, rtol=1e-5)
        print("solve(mesh=) auto + solve_many OK")
    """)
