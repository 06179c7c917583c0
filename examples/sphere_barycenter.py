"""Paper Fig. 6 / Remark 1: Wasserstein barycenters on the positive sphere
with the cost c(x, y) = -log(x^T y).

    PYTHONPATH=src python examples/sphere_barycenter.py

On the positive sphere the Gibbs kernel of this cost at eps=1 is the
LINEAR kernel k(x,y) = x^T y — i.e. the positive feature map is the
identity, phi(x) = x, with r = 3 features. Sinkhorn iterations therefore
cost O(3n) — the most extreme instance of the paper's factorization.

We discretize the positive octant (50x50), place three blurred corner
histograms (the paper's a, b, c), and run iterative Bregman projections
[Benamou et al. '15] entirely through the factored kernel to compute
their barycenter. A softmax sharpening reveals the barycenter mass
concentrates between the corners, as in the paper's panel (e).
"""
import jax
import jax.numpy as jnp
import numpy as np


def positive_sphere_grid(m=50):
    th = jnp.linspace(0.02, jnp.pi / 2 - 0.02, m)
    ph = jnp.linspace(0.02, jnp.pi / 2 - 0.02, m)
    T, P = jnp.meshgrid(th, ph)
    pts = jnp.stack([
        jnp.sin(T) * jnp.cos(P), jnp.sin(T) * jnp.sin(P), jnp.cos(T)
    ], axis=-1).reshape(-1, 3)
    return pts  # (m*m, 3) on the positive sphere


def corner_hist(pts, corner, sharp=60.0):
    w = jnp.exp(sharp * (pts @ corner - 1.0))
    return w / jnp.sum(w)


def barycenter_ibp(Phi, hists, n_iter=200):
    """IBP barycenter through the factored kernel K = Phi Phi^T (r=3)."""
    n, _ = Phi.shape
    K = lambda v: Phi @ (Phi.T @ v)          # O(3n) matvec
    KT = K                                   # symmetric
    u = jnp.ones((len(hists), n))
    v = jnp.ones((len(hists), n))

    def body(carry, _):
        u, v = carry
        Ktu = jax.vmap(lambda ui: KT(ui))(u)              # (k, n)
        logb = jnp.mean(jnp.log(jnp.maximum(v * Ktu, 1e-38)), axis=0)
        b = jnp.exp(logb)
        v = b[None, :] / jnp.maximum(Ktu, 1e-38)
        Kv = jax.vmap(lambda vi: K(vi))(v)
        u = jnp.stack(hists) / jnp.maximum(Kv, 1e-38)
        return (u, v), b

    (u, v), bs = jax.lax.scan(body, (u, v), None, length=n_iter)
    return bs[-1]


def main():
    pts = positive_sphere_grid(50)
    corners = [jnp.array(c, jnp.float32) for c in
               ([1, 0, 0], [0, 1, 0], [0, 0, 1])]
    hists = [corner_hist(pts, c) for c in corners]
    b = jax.jit(lambda: barycenter_ibp(pts, hists))()
    # softmax sharpening (paper temperature 1000)
    sharp = jax.nn.softmax(1000.0 * b / jnp.max(b))
    peak = pts[jnp.argmax(sharp)]
    center = jnp.array([1.0, 1.0, 1.0]) / jnp.sqrt(3.0)
    ang = float(jnp.degrees(jnp.arccos(jnp.clip(peak @ center, -1, 1))))
    print(f"barycenter mass peak at {np.asarray(peak).round(3)} "
          f"({ang:.1f} deg from the octant center — mass sits between "
          f"the three corners, paper Fig. 6e)")
    mass_near_center = float(jnp.sum(jnp.where(pts @ center > 0.95, b, 0.0))
                             / jnp.sum(b))
    print(f"fraction of barycenter mass within 18deg of center: "
          f"{mass_near_center:.2f}")
    assert ang < 25.0, "barycenter should concentrate mid-octant"
    print("OK — factored-kernel (r=3) barycenter via IBP")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
