"""End-to-end driver: train a ~100M-param LM for a few hundred steps with
the paper's Sinkhorn-divergence loss in the objective (DESIGN.md §4).

    PYTHONPATH=src python examples/train_lm.py --steps 300          # ~100M
    PYTHONPATH=src python examples/train_lm.py --steps 200 --tiny   # CI-fast

Uses the production stack end to end: config system (smollm-135m family),
deterministic data pipeline, AdamW + cosine schedule, checkpointing +
fault-tolerant supervisor, OT prototype loss (learned positive features).
"""
import argparse
import dataclasses
import math
import time

import jax

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core.objective import ExecutionPolicy
from repro.data import DataConfig, DataPipeline
from repro.distributed.fault_tolerance import (
    FaultToleranceConfig,
    TrainingSupervisor,
)
from repro.kernels.ops import observe_plan_selection
from repro.models import init_params, param_count, train_loss
from repro.optim import (
    AdamWConfig,
    adamw_update,
    init_adamw,
    linear_warmup_cosine,
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=6e-4)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--arch", default="smollm_135m",
                    help="config name (e.g. deepseek-v2-236b for the "
                    "sinkhorn-router MoE path)")
    ap.add_argument("--no-ot", action="store_true",
                    help="ablation: drop the Sinkhorn loss")
    ap.add_argument("--router", default=None,
                    choices=("softmax", "sinkhorn"),
                    help="override the config's MoE router")
    ap.add_argument("--strict", action="store_true",
                    help="CI mode: force the fused bf16 plan (interpret), "
                    "assert plan selection, finite losses and zero "
                    "post-warmup retraces")
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.tiny:
        cfg = cfg.tiny()
    else:
        # ~100M-class config: shorter depth for CPU speed
        cfg = dataclasses.replace(cfg, n_layers=8, ot_iters=20,
                                  ot_tokens=256)
    if args.no_ot:
        cfg = dataclasses.replace(cfg, ot_loss_weight=0.0)
    if args.router:
        cfg = dataclasses.replace(cfg, router=args.router)
    if args.strict:
        # force the fused megakernel path even on interpret-only backends
        # so plan-selection observability can verify the policy is active
        cfg = dataclasses.replace(cfg, ot_use_pallas=True)

    # the run-wide OT execution policy: constructed ONCE from the config +
    # resolved backend, shared by the prototype loss and sinkhorn router
    policy = ExecutionPolicy.from_config(cfg)

    key = jax.random.PRNGKey(0)
    params = init_params(key, cfg)
    print(f"[train_lm] arch={cfg.name}({'tiny' if args.tiny else '8L'}) "
          f"params={param_count(params) / 1e6:.1f}M "
          f"ot_loss={'off' if args.no_ot else cfg.ot_loss_weight} "
          f"router={cfg.router}")
    print(f"[train_lm] ot-policy {policy.describe()}")

    ocfg = AdamWConfig(lr=args.lr)
    opt_state = init_adamw(params, ocfg)
    sched = linear_warmup_cosine(args.lr, warmup=20, total_steps=args.steps)
    data = DataPipeline(DataConfig(
        seed=0, global_batch=args.batch, seq_len=args.seq, vocab=cfg.vocab))

    @jax.jit
    def step_fn(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            lambda p: train_loss(p, cfg, batch, policy=policy),
            has_aux=True)(params)
        params, opt_state, om = adamw_update(params, grads, opt_state,
                                             ocfg, lr_schedule=sched)
        metrics.update(om)
        return params, opt_state, metrics

    if args.strict:
        # warm up under the observability hook: the trace must select the
        # fused plan with the policy's precision for the prototype loss
        with observe_plan_selection() as plan_events:
            b0 = DataPipeline(DataConfig(
                seed=0, global_batch=args.batch, seq_len=args.seq,
                vocab=cfg.vocab)).batch_at(0)
            step_fn(params, opt_state, b0)
        if cfg.ot_loss_weight > 0:
            sel = [e for e in plan_events
                   if e["geometry"] == "FactoredPositive"]
            assert sel, f"no fused plan for the OT loss: {plan_events}"
            assert all(e["precision"] == cfg.ot_precision for e in sel), sel
            print(f"[train_lm] strict: fused plan active "
                  f"({sel[0]['kind']}/{sel[0]['mode']}, "
                  f"precision={sel[0]['precision']}, {len(sel)} solves)")

    ckpt = CheckpointManager(args.ckpt_dir, keep=2, async_save=True)
    sup = TrainingSupervisor(ckpt, FaultToleranceConfig(save_every=100))
    t0 = time.time()
    hist = []

    def one_step(state, step):
        params, opt_state = state
        new_params, new_opt, m = step_fn(params, opt_state,
                                         data.batch_at(step))
        mm = {k: float(v) for k, v in jax.device_get(m).items()}
        if not sup.admit_step(mm):
            # non-finite OT loss / grad norm: applying this update would
            # poison the parameters permanently — keep the OLD state and
            # train on the next batch (the supervisor bounds the streak)
            print(f"[train_lm] step {step:4d} SKIPPED on non-finite "
                  f"metrics (streak {sup.consecutive_skips})")
            return params, opt_state
        if step % 20 == 0:
            hist.append(mm)
            print(f"[train_lm] step {step:4d} loss {mm['loss']:.4f} "
                  f"ce {mm['ce']:.4f} ot {mm.get('ot', 0):.4f} "
                  f"lr {mm['lr']:.2e} ({time.time() - t0:.0f}s)")
        return new_params, new_opt

    traces_after_warmup = step_fn._cache_size() if args.strict else None
    (params, opt_state), end = sup.run((params, opt_state), 0, args.steps,
                                       one_step)
    first, last = hist[0]["ce"], hist[-1]["ce"]
    print(f"[train_lm] CE {first:.4f} -> {last:.4f} over {end} steps "
          f"({'improved' if last < first else 'NO IMPROVEMENT'}); "
          f"checkpoints in {args.ckpt_dir}")
    if args.strict:
        assert all(math.isfinite(m[k]) for m in hist for k in m), hist
        retraces = step_fn._cache_size() - traces_after_warmup
        assert retraces == 0, f"{retraces} post-warmup retraces"
        assert sup.skipped_steps == 0, (
            f"{sup.skipped_steps} steps skipped on non-finite metrics in "
            "a clean run")
        print(f"[train_lm] strict: all losses finite, 0 skipped steps, "
              f"0 post-warmup retraces ({step_fn._cache_size()} trace)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
