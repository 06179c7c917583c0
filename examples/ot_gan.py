"""OT-GAN with adversarially-learned positive-feature kernels (paper §4).

    PYTHONPATH=src python examples/ot_gan.py [--steps 300] [--pixels]

Reproduces the paper's Eq. (18) objective at container scale:

    min_rho  max_{gamma, theta}  (1/B) sum_b  Wbar_{eps, c_theta o h_gamma}

* g_rho   — generator MLP z -> x
* f_gamma — adversarial embedding x -> R^d_latent  (the "cost" tower)
* phi_theta — Lemma-1 Gaussian positive features with LEARNED anchors

The whole loss is ONE ``OTObjective``: the embedded clouds and learnable
anchors become a ``GaussianPointCloud`` geometry, the divergence runs
through the shared execution stack (fused megakernel + bf16 under the
training :class:`ExecutionPolicy`), and gradients flow through the
envelope-theorem VJP — both of the paper's claimed advantages (linear
batch cost; no unrolled loop in the backward graph).

Default target: 8-mode Gaussian ring in R^2 (mode coverage printed).
--pixels switches to a 12x12 synthetic "two-moons pixels" image domain to
exercise the DCGAN-shaped pipeline (conv stubs replaced by MLPs on CPU).

--eval-kernel prints the Table-1 analogue: learned kernel values between
data/data, data/noise, noise/noise pairs.

--strict is the CI train-smoke contract: assert the fused bf16 plan was
selected (plan observability), all losses finite, zero post-warmup
retraces, and a decreasing divergence trend.
"""
import argparse
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.objective import ExecutionPolicy, OTObjective
from repro.core.features import GaussianFeatureMap, gaussian_log_features
from repro.kernels.ops import observe_plan_selection
from repro.models.layers import init_linear, linear

LATENT_Z = 16
LATENT_D = 8         # f_gamma output dim (the paper embeds into R^d)
EPS = 0.5
R_BALL = 3.0


def init_mlp_stack(key, dims, std=None):
    ks = jax.random.split(key, len(dims) - 1)
    return [init_linear(k, a, b, bias=True,
                        std=(std or (2.0 / a) ** 0.5))
            for k, a, b in zip(ks, dims[:-1], dims[1:])]


def mlp_apply(stack, x, final_tanh=False):
    for i, p in enumerate(stack):
        x = linear(p, x)
        if i < len(stack) - 1:
            x = jax.nn.gelu(x)
    return jnp.tanh(x) if final_tanh else x


def make_data(key, n, pixels=False):
    if pixels:
        # two-moons rendered to 12x12 binary-ish images
        k1, k2 = jax.random.split(key)
        t = jnp.pi * jax.random.uniform(k1, (n,))
        moon = jax.random.bernoulli(k2, 0.5, (n,))
        cx = jnp.where(moon, 0.5 + 0.4 * jnp.cos(t), 0.5 - 0.4 * jnp.cos(t))
        cy = jnp.where(moon, 0.35 + 0.3 * jnp.sin(t), 0.65 - 0.3 * jnp.sin(t))
        gx, gy = jnp.meshgrid(jnp.linspace(0, 1, 12), jnp.linspace(0, 1, 12))
        img = jnp.exp(-(((gx[None] - cx[:, None, None]) ** 2
                         + (gy[None] - cy[:, None, None]) ** 2) / 0.01))
        return img.reshape(n, 144)
    # ring of 8 gaussians
    k1, k2 = jax.random.split(key)
    mode = jax.random.randint(k1, (n,), 0, 8)
    ang = 2 * jnp.pi * mode / 8
    centers = jnp.stack([jnp.cos(ang), jnp.sin(ang)], -1) * 2.0
    return centers + 0.05 * jax.random.normal(k2, (n, 2))


def embed(f, pts):
    """h_gamma: the adversarial tower into B(0, R_BALL)."""
    return mlp_apply(f, pts, final_tanh=True) * R_BALL


def gan_losses(params, key, data, obj: OTObjective):
    """Eq. 18 inner term as ONE objective call: geometry from the embedded
    clouds + learnable anchors, divergence under the shared policy."""
    g, f, anchors = params["gen"], params["emb"], params["anchors"]
    B = data.shape[0]
    z = jax.random.normal(key, (B, LATENT_Z))
    fake = mlp_apply(g, z)
    geom = obj.gaussian(embed(f, fake), embed(f, data), anchors, R=R_BALL)
    return obj.divergence(geom), fake


def mode_coverage(fake):
    ang = jnp.arctan2(fake[:, 1], fake[:, 0])
    mode = jnp.round(ang / (2 * jnp.pi / 8)).astype(jnp.int32) % 8
    radius_ok = jnp.abs(jnp.linalg.norm(fake[:, :2], axis=1) - 2.0) < 0.5
    covered = jnp.zeros((8,)).at[mode].max(radius_ok.astype(jnp.float32))
    return int(jnp.sum(covered))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--r", type=int, default=128)
    ap.add_argument("--iters", type=int, default=40,
                    help="Sinkhorn iterations per solve")
    ap.add_argument("--nc", type=int, default=3,
                    help="adversary steps per generator step (paper's n_c)")
    ap.add_argument("--pixels", action="store_true")
    ap.add_argument("--eval-kernel", action="store_true")
    ap.add_argument("--strict", action="store_true",
                    help="CI mode: force the fused bf16 plan and assert "
                    "plan selection, finite losses, zero post-warmup "
                    "retraces, decreasing divergence")
    args = ap.parse_args()

    x_dim = 144 if args.pixels else 2
    key = jax.random.PRNGKey(0)
    kg, ke, ka, kd = jax.random.split(key, 4)
    fm = GaussianFeatureMap(r=args.r, d=LATENT_D, eps=EPS, R=R_BALL)
    params = {
        "gen": init_mlp_stack(kg, [LATENT_Z, 128, 128, x_dim]),
        "emb": init_mlp_stack(ke, [x_dim, 64, LATENT_D]),
        "anchors": fm.init(ka),
    }

    # ONE objective per run: geometry construction, divergence, envelope
    # VJP and execution policy (bf16 factors; fused plan auto on compiled
    # backends, forced interpret-mode in --strict so CI verifies it)
    policy = ExecutionPolicy.training(
        use_pallas=True if args.strict else None)
    obj = OTObjective(eps=EPS, tol=0.0, max_iter=args.iters, policy=policy)
    print(f"[ot-gan] ot-policy {policy.describe()}")

    from functools import partial

    @partial(jax.jit, static_argnames=("adv",))
    def train_step(params, key, data, lr_g=3e-3, lr_adv=1e-3, adv=False):
        def loss_fn(p):
            d, fake = gan_losses(p, key, data, obj)
            return d, fake
        (d, fake), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        sign = {"gen": -1.0, "emb": +1.0, "anchors": +1.0}
        new = {}
        for name in params:
            lr = lr_g if name == "gen" else lr_adv
            s = sign[name] * lr
            upd = (lambda p_, g_: p_ + s * g_)
            if adv and name == "gen":
                new[name] = params[name]
            elif (not adv) and name != "gen":
                new[name] = params[name]
            else:
                new[name] = jax.tree.map(upd, params[name], grads[name])
        return new, d, fake

    if args.strict:
        # warm both trace variants under the observability hook: the GAN
        # loss must run through the fused plan at the policy's precision
        with observe_plan_selection() as events:
            kw, kb = jax.random.split(kd)
            data0 = make_data(kb, args.batch, pixels=args.pixels)
            train_step(params, kw, data0, adv=True)
            train_step(params, kw, data0, adv=False)
        sel = [e for e in events if e["geometry"] == "GaussianPointCloud"]
        assert sel, f"no fused plan selected for the GAN loss: {events}"
        assert all(e["precision"] == "bf16" for e in sel), sel
        print(f"[ot-gan] strict: fused plan active "
              f"({sel[0]['kind']}/{sel[0]['mode']}, precision=bf16, "
              f"{len(sel)} solves/trace)")
        traces0 = train_step._cache_size()

    t0 = time.time()
    divergences = []
    for step in range(args.steps):
        kd, ks, kb = jax.random.split(kd, 3)
        data = make_data(kb, args.batch, pixels=args.pixels)
        adv = bool((step % (args.nc + 1)) != args.nc)  # n_c adversary : 1 gen
        params, d, fake = train_step(params, ks, data, adv=adv)
        divergences.append(float(d))
        if step % 50 == 0 or step == args.steps - 1:
            msg = f"[ot-gan] step {step:4d} Wbar={float(d):+.4f}"
            if not args.pixels:
                msg += f" modes={mode_coverage(fake)}/8"
            print(msg + f" ({time.time() - t0:.1f}s)")

    if args.strict:
        assert all(math.isfinite(d) for d in divergences), "non-finite Wbar"
        retraces = train_step._cache_size() - traces0
        assert retraces == 0, f"{retraces} post-warmup retraces"
        k = max(5, args.steps // 10)
        head = float(np.mean(divergences[:k]))
        tail = float(np.mean(divergences[-k:]))
        assert tail < head, (
            f"divergence did not decrease: first-{k} mean {head:.4f} "
            f"-> last-{k} mean {tail:.4f}")
        print(f"[ot-gan] strict: finite losses, 0 post-warmup retraces, "
              f"Wbar {head:.4f} -> {tail:.4f} (decreasing)")

    if args.eval_kernel:
        # Table-1 analogue: learned kernel geometry
        kd1, kd2 = jax.random.split(kd)
        data = make_data(kd1, 64, pixels=args.pixels)
        noise = jax.random.normal(kd2, (64, x_dim))

        def k_mean(p, q_):
            lp = gaussian_log_features(
                embed(params["emb"], p), params["anchors"], eps=EPS, q=fm.q)
            lq = gaussian_log_features(
                embed(params["emb"], q_), params["anchors"], eps=EPS, q=fm.q)
            return float(jnp.mean(jnp.exp(lp) @ jnp.exp(lq).T))
        print("learned kernel k_theta(f(x), f(y)) means "
              "(Table 1 analogue):")
        print(f"  data/data   = {k_mean(data, data):.4e}")
        print(f"  data/noise  = {k_mean(data, noise):.4e}")
        print(f"  noise/noise = {k_mean(noise, noise):.4e}")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
