"""MoE: EP shard_map path vs dense oracle; Sinkhorn routing balance."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.routing import sinkhorn_route
from repro.models.moe import init_moe, moe_dense, moe_ep_local, router_probs


def _setup(T=64, d=16, f=32, E=8, seed=0):
    key = jax.random.PRNGKey(seed)
    p = init_moe(key, d, f, E)
    x = jax.random.normal(jax.random.fold_in(key, 1), (T, d)) * 0.5
    return p, x


def test_ep_matches_dense_single_rank():
    """With 1 rank and ample capacity, EP must equal the dense path exactly
    (same experts, same gates; no drops)."""
    p, x = _setup()
    out_d, aux_d = moe_dense(p, x, top_k=2)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    fn = jax.shard_map(
        lambda p_, x_: moe_ep_local(p_, x_, top_k=2, n_experts=8,
                                    axis="model", capacity_factor=8.0),
        mesh=mesh,
        in_specs=({"router": P(None, None), "up": P("model", None, None),
                   "gate": P("model", None, None),
                   "down": P("model", None, None)}, P(None, None)),
        out_specs=(P(None, None), P()),
        check_vma=False,
    )
    with mesh:
        out_e, aux_e = fn(p, x)
    np.testing.assert_allclose(np.asarray(out_e), np.asarray(out_d),
                               rtol=2e-4, atol=2e-5)


def test_ep_gradients_flow():
    p, x = _setup()
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))

    def loss(p_, x_):
        fn = jax.shard_map(
            lambda pp, xx: moe_ep_local(pp, xx, top_k=2, n_experts=8,
                                        axis="model", capacity_factor=8.0),
            mesh=mesh,
            in_specs=({"router": P(None, None),
                       "up": P("model", None, None),
                       "gate": P("model", None, None),
                       "down": P("model", None, None)}, P(None, None)),
            out_specs=(P(None, None), P()),
            check_vma=False,
        )
        out, aux = fn(p_, x_)
        return jnp.sum(out ** 2) + 0.01 * aux

    with mesh:
        g = jax.grad(loss)(p, x)
    norms = {k: float(jnp.linalg.norm(v)) for k, v in
             jax.tree_util.tree_flatten_with_path(g)[0] and
             [(str(kp), jnp.linalg.norm(l)) for kp, l in
              jax.tree_util.tree_flatten_with_path(g)[0]]}
    for k, v in norms.items():
        assert np.isfinite(v), k
    assert norms and any(v > 0 for v in norms.values())


def test_capacity_drops_bounded():
    """Adversarial routing (all tokens to one expert) must drop to capacity,
    not corrupt outputs."""
    p, x = _setup(T=32)
    # rig the router so every token picks expert 0 hardest
    p = dict(p)
    p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(5.0)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                ("data", "model"))
    fn = jax.shard_map(
        lambda p_, x_: moe_ep_local(p_, x_, top_k=1, n_experts=8,
                                    axis="model", capacity_factor=0.25),
        mesh=mesh,
        in_specs=({"router": P(None, None), "up": P("model", None, None),
                   "gate": P("model", None, None),
                   "down": P("model", None, None)}, P(None, None)),
        out_specs=(P(None, None), P()),
        check_vma=False,
    )
    with mesh:
        out, aux = fn(p, x)
    assert bool(jnp.all(jnp.isfinite(out)))
    # dropped tokens produce zero rows
    nz = jnp.sum(jnp.any(out != 0, axis=-1))
    assert int(nz) < 32


def test_sinkhorn_router_balances_load():
    """The paper-integrated router: balanced assignment beats raw softmax
    top-k load imbalance on skewed logits."""
    key = jax.random.PRNGKey(0)
    T, E, k = 256, 8, 2
    skew = jnp.array([3.0, 1.0] + [0.0] * (E - 2))
    logits = jax.random.normal(key, (T, E)) + skew[None, :]
    r = sinkhorn_route(logits, top_k=k, eps=0.3, n_iter=50)
    load_sink = jnp.mean(r.dispatch, axis=0)
    probs = jax.nn.softmax(logits, -1)
    _, idx = jax.lax.top_k(probs, k)
    disp = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], idx].set(1.0)
    load_soft = jnp.mean(disp, axis=0)
    imb = lambda l: float(jnp.max(l) / jnp.maximum(jnp.mean(l), 1e-9))
    assert imb(load_sink) < imb(load_soft), (load_sink, load_soft)


def test_router_probs_topk_structure():
    p, x = _setup()
    for router in ("softmax", "sinkhorn"):
        combine, aux = router_probs(p, x, top_k=2, router=router)
        nz = jnp.sum(combine > 0, axis=-1)
        assert bool(jnp.all(nz <= 2))
        np.testing.assert_allclose(np.asarray(jnp.sum(combine, -1)),
                                   np.ones(x.shape[0]), atol=1e-5)
        assert np.isfinite(float(aux))
