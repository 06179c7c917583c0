"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--pallas]
                                            [--json BENCH_quick.json]

Emits ``name,us_per_call,derived`` CSV rows:
  tradeoff/*   — Fig. 1/3/5  RF vs Nys vs Sin time-accuracy
  scaling/*    — §3.1        O(r(n+m)) vs O(nm) per-iteration scaling
  gan_step/*   — §4          GAN loss+grad step time: OTObjective
                 (positive features, bf16 training policy) vs dense
                 Sinkhorn baseline, with loss-parity rows (``--gan``
                 additionally gates the speedup >= 2x)
  solver/*     — Alg. 1      fused-kernel iteration microbench
  batch/*      — api.py      vmapped BatchedSinkhorn vs per-problem loop
  */pallas*    — kernels.ops fused-plan vs XLA parity + iteration counts
                 (``--pallas``; interpret mode off-TPU, compiled on TPU)
  roofline/*   — §Roofline   dry-run derived terms per (arch x shape x mesh)
  serve/*      — serving     OTService open-loop latency, warm-start hit
                 rates, batched/warm capacity vs per-request engine loop,
                 zero-recompile gate (``--serve``)
  stream/*     — streaming   incremental warm re-solve vs full cold
                 rebuild after a <= 5% support mutation (``--stream``;
                 speedup >= 5x and zero post-warmup retraces gated)
  */tuned*     — autotuner   measured block shapes vs the static pick_block
                 prior (``--tune``); ratio >= 1.0 gated, warm-cache runs
                 gated to zero timing trials (``--tune-expect-cached``)

``--quick`` is the tier-1 smoke entry: CPU-sized problems, minutes total.
``--json PATH`` additionally writes the rows as a ``BENCH_*.json`` artifact
(CI uploads it per-PR so the perf trajectory accumulates).
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import platform
import sys
import time

import jax
import jax.numpy as jnp


def bench_solver_iteration():
    """Microbench of the paper's hot loop at production-ish sizes."""
    from repro.core import sinkhorn_factored
    key = jax.random.PRNGKey(0)
    print_rows = []
    for n, r in ((4096, 256), (16384, 256), (16384, 1024)):
        xi = jax.random.uniform(key, (n, r)) + 0.05
        zt = jax.random.uniform(jax.random.fold_in(key, 1), (n, r)) + 0.05
        a = jnp.full((n,), 1.0 / n)
        iters = 20
        fn = jax.jit(lambda xi_, zt_: sinkhorn_factored(
            xi_, zt_, a, a, eps=0.5, tol=0.0, max_iter=iters).u)
        fn(xi, zt).block_until_ready()
        t0 = time.perf_counter()
        fn(xi, zt).block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        flops = 4.0 * n * r  # 2 thin matvecs fwd
        print_rows.append(
            f"solver/iter/n{n}_r{r},{dt * 1e6:.1f},gflops_s="
            f"{flops / dt / 1e9:.2f}")
    return print_rows


def bench_fused_loop(inner_steps: int = 8, quick: bool = False):
    """Megakernel (persistent multi-iteration block) vs the per-iteration
    fused plan, us/iter at the ``solver/iter`` shapes.

    Both sides run the SAME plan-step semantics through Pallas (interpret
    off-TPU, compiled Mosaic on TPU): the unfused side dispatches 4-5
    kernels per iteration and round-trips every intermediate; the fused
    side runs ``inner_steps`` whole iterations in ONE launch with the
    factors VMEM-resident. The us/iter RATIO is therefore a same-machine
    launch-and-traffic-overhead measurement that transfers across runner
    generations (like the batched-speedup gate); off-TPU it bounds
    dispatch overhead, on TPU it adds the HBM-refetch saving. Returns
    (rows, best_ratio).
    """
    from repro.core.geometry import FactoredPositive
    from repro.kernels.ops import geometry_ops

    key = jax.random.PRNGKey(0)
    rows, best = [], 0.0
    shapes = ((4096, 256), (16384, 256)) if quick \
        else ((4096, 256), (16384, 256), (16384, 1024))
    for n, r in shapes:
        xi = jax.random.uniform(key, (n, r)) + 0.05
        zt = jax.random.uniform(jax.random.fold_in(key, 1), (n, r)) + 0.05
        a = jnp.full((n,), 1.0 / n)
        geom = FactoredPositive(xi=xi, zeta=zt, eps=0.5)
        shape = f"n{n}_r{r}"
        flops = 8.0 * n * r          # 4 thin matvecs per full iteration

        def timed(fn):
            out = fn()
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            jax.block_until_ready(fn())
            return (time.perf_counter() - t0) / inner_steps

        variants = []
        for prec in ("highest", "bf16"):
            plan = geometry_ops(geom, mode="scaling",
                                precision=prec)
            block = plan.make_block_step(a, a, inner_steps=inner_steps)
            if block is None:        # over the compiled-VMEM budget
                continue
            step, init = block
            u0, v0 = jnp.ones((n,)), jnp.ones((n,))

            @jax.jit
            def run_block(u0=u0, v0=v0, init=init, step=step):
                (u, _, _), err = step(init(u0, v0))
                return u, err

            suffix = "" if prec == "highest" else "_bf16"
            variants.append((f"fused_block{suffix}", timed(run_block)))

        plan = geometry_ops(geom, mode="scaling")
        pstep, pinit = plan.make_step(a, a)

        @jax.jit
        def run_unfused(u0=jnp.ones((n,)), v0=jnp.ones((n,)),
                        pinit=pinit, pstep=pstep):
            carry = pinit(u0, v0)
            for _ in range(inner_steps):
                carry, err = pstep(carry)
            return carry[0], err

        dt_unfused = timed(run_unfused)
        rows.append(f"solver/iter/{shape}/unfused_plan,"
                    f"{dt_unfused * 1e6:.1f},gflops_s="
                    f"{flops / dt_unfused / 1e9:.2f}")
        for name, dt in variants:
            rows.append(f"solver/iter/{shape}/{name},{dt * 1e6:.1f},"
                        f"inner_steps={inner_steps};gflops_s="
                        f"{flops / dt / 1e9:.2f}")
            if name == "fused_block":
                ratio = dt_unfused / dt
                best = max(best, ratio)
                rows.append(f"solver/fused_speedup/{shape},0,"
                            f"ratio={ratio:.2f}")
    return rows, best


def bench_autotune(quick: bool = False, inner_steps: int = 8,
                   expect_cached: bool = False):
    """Autotuned vs static block shapes on the streaming per-iteration
    plan, us/iter at the ``solver/iter`` shapes.

    The tuned side resolves its blocks through ``kernels.autotune`` with
    measured tuning enabled (cache honored — a warm ``REPRO_TUNING_CACHE``
    means zero timing trials); the static side is the deterministic
    ``pick_block`` prior. When the tuner lands exactly on the static plan
    the ratio is emitted as exactly 1.0 without re-timing (the static
    plan is always among the candidates, so the tuner cannot lose — the
    ratio gate enforces that invariant end to end).

    ``expect_cached=True`` additionally asserts resolution stability: a
    second plan built against the warm cache must not add entries to the
    inner kernel jit caches (zero retraces). Returns
    ``(rows, worst_ratio, trials, failures)``.
    """
    from repro.core.geometry import FactoredPositive
    from repro.kernels import autotune, feature_map, kermatvec
    from repro.kernels.backend import resolve_backend
    from repro.kernels.ops import geometry_ops

    def impl_cache_sizes():
        return tuple(fn._cache_size() for fn in (
            kermatvec._feature_contract_impl,
            kermatvec._halfstep_impl,
            kermatvec._matvec_impl,
            feature_map._feature_map_impl,
        ))

    key = jax.random.PRNGKey(0)
    be = resolve_backend()
    rows, failures = [], []
    worst = None
    autotune.reset_stats()
    shapes = ((4096, 256), (16384, 256)) if quick \
        else ((4096, 256), (16384, 256), (16384, 1024))
    for n, r in shapes:
        xi = jax.random.uniform(key, (n, r)) + 0.05
        zt = jax.random.uniform(jax.random.fold_in(key, 1), (n, r)) + 0.05
        a = jnp.full((n,), 1.0 / n)
        geom = FactoredPositive(xi=xi, zeta=zt, eps=0.5)
        shape = f"n{n}_r{r}"
        flops = 8.0 * n * r

        def make_runner(plan, n=n):
            step, init = plan.make_step(a, a)

            @jax.jit
            def run(u0=jnp.ones((n,)), v0=jnp.ones((n,)),
                    init=init, step=step):
                carry = init(u0, v0)
                for _ in range(inner_steps):
                    carry, err = step(carry)
                return carry[0], err

            return run

        def timed(fn, reps=3):
            jax.block_until_ready(fn())          # compile (uncounted)
            ts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                jax.block_until_ready(fn())
                ts.append((time.perf_counter() - t0) / inner_steps)
            return min(ts)

        extents = {"n": n, "r": r, "B": 1}
        static_blocks = (autotune.static_plan("feature_contract", extents,
                                              be),
                         autotune.static_plan("feature_rows", extents, be))
        with autotune.tuning():
            tuned_blocks = (
                autotune.resolve("feature_contract", extents, xi.dtype, be),
                autotune.resolve("feature_rows", extents, xi.dtype, be))
            dt_tuned = timed(make_runner(geometry_ops(geom)))
            if expect_cached:
                sizes = impl_cache_sizes()
                jax.block_until_ready(make_runner(geometry_ops(geom))())
                if impl_cache_sizes() != sizes:
                    failures.append(
                        f"tuned plan at {shape} retraced inner kernels on "
                        "a warm cache (resolution unstable)")
        blocks_repr = ";".join(
            f"{k}={v}" for plan in tuned_blocks
            for k, v in sorted(plan.items()))
        rows.append(f"solver/iter/{shape}/tuned,{dt_tuned * 1e6:.1f},"
                    f"{blocks_repr};gflops_s={flops / dt_tuned / 1e9:.2f}")
        if tuned_blocks == static_blocks:
            ratio = 1.0              # same plan — no noisy re-timing
        else:
            dt_static = timed(make_runner(geometry_ops(geom)))
            ratio = round(dt_static / dt_tuned, 2)
        rows.append(f"solver/tuned_ratio/{shape},0,ratio={ratio:.2f};"
                    f"same_plan={tuned_blocks == static_blocks}")
        worst = ratio if worst is None else min(worst, ratio)
    stats = autotune.stats()
    rows.append(f"tune/trials,0,trials={stats['trials']};"
                f"keys_tuned={stats['keys_tuned']};"
                f"disk_hits={stats['disk_hits']};backend={be.name}")
    return rows, worst, stats["trials"], failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--skip-tradeoff", action="store_true")
    ap.add_argument("--pallas", action="store_true",
                    help="add the fused-plan parity axes (bench_batch "
                         "--pallas, bench_tradeoff --pallas)")
    ap.add_argument("--serve", action="store_true",
                    help="add the serving axis (bench_serve open-loop "
                         "latency, batched/warm capacity, zero-recompile "
                         "gate)")
    ap.add_argument("--stream", action="store_true",
                    help="add the streaming axis (bench_stream: paged "
                         "store + warm re-solve vs full cold rebuild; "
                         "gates speedup >= 5x and zero retraces)")
    ap.add_argument("--gan", action="store_true",
                    help="gate the GAN-step axis: objective-vs-dense "
                         "speedup >= 2x at the quick shapes (the parity "
                         "rows are hard-gated via match=False regardless)")
    ap.add_argument("--tune", action="store_true",
                    help="add the autotuner axis (bench_autotune: tuned "
                         "vs static block shapes, ratio >= 1.0 gate; "
                         "cache honors REPRO_TUNING_CACHE)")
    ap.add_argument("--tune-expect-cached", action="store_true",
                    help="with --tune: assert the tuning cache is warm — "
                         "zero timing trials and zero inner-kernel "
                         "retraces, else fail")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write the rows as a BENCH_*.json artifact")
    ap.add_argument("--baseline", metavar="PATH", default=None,
                    help="committed BENCH_*.json to gate against: fail on "
                         ">25%% batched-speedup regression (speedup is a "
                         "same-machine ratio, so it transfers across "
                         "runner generations where raw us/call does not)")
    args = ap.parse_args()

    rows: list = []

    def section(title):
        print(f"# --- {title} ---", file=sys.stderr)

    def emit(text: str) -> None:
        # strip each sub-benchmark's own CSV header so stdout stays the
        # single-header stream documented above
        kept = [l for l in text.splitlines()
                if l.strip() and not l.startswith("name,")]
        rows.extend(l for l in kept if not l.startswith("#"))
        if kept:
            print("\n".join(kept))

    print("name,us_per_call,derived")

    section("solver microbench")
    for row in bench_solver_iteration():
        emit(row)

    fused_speedup = None
    if args.pallas:
        section("megakernel vs per-iteration fused plan (kernels.fused_loop)")
        fused_rows, fused_speedup = bench_fused_loop(quick=args.quick)
        for row in fused_rows:
            emit(row)
        print(f"# fused-block speedup {fused_speedup:.2f}x "
              "(target >= 1.5x)", file=sys.stderr)

    tuned_ratio = tune_trials = None
    tune_failures: list = []
    if args.tune:
        section("autotuned vs static tiling (kernels.autotune)")
        tune_rows, tuned_ratio, tune_trials, tune_failures = bench_autotune(
            quick=args.quick, expect_cached=args.tune_expect_cached)
        for row in tune_rows:
            emit(row)
        print(f"# tuned-vs-static worst ratio {tuned_ratio:.2f}x "
              f"(target >= 1.0); {tune_trials} timing trials",
              file=sys.stderr)

    section("scaling (linear vs quadratic, Sec 3.1)")
    from . import bench_scaling
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench_scaling.main(n_list=(500, 1000, 2000) if args.quick
                           else (500, 1000, 2000, 4000))
    emit(buf.getvalue())

    if not args.skip_tradeoff:
        section("tradeoff (Fig 1/3/5)")
        from . import bench_tradeoff
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            bench_tradeoff.main(n=1000 if args.quick else 1200,
                                quick=args.quick)
        emit(buf.getvalue())

    section("geometry families (Geometry protocol, tradeoff --geometry)")
    from . import bench_tradeoff as bt
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bt.main(n=512 if args.quick else 1024, quick=args.quick,
                geometry=True)
    emit(buf.getvalue())

    if args.pallas:
        section("fused-plan parity (solve --pallas axis)")
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            bt.main(n=256 if args.quick else 512, quick=args.quick,
                    pallas=True)
        emit(buf.getvalue())

    section("batched engine vs per-problem loop (api.BatchedSinkhorn)")
    from . import bench_batch
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        speedup = bench_batch.main(quick=args.quick, pallas=args.pallas)
    emit(buf.getvalue())
    print(f"# batched speedup {speedup:.2f}x (target >= 3x)", file=sys.stderr)

    serve_speedup = serve_recompiles = None
    if args.serve:
        section("serving (OTService open loop + capacity, bench_serve)")
        from . import bench_serve
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            serve_speedup, serve_recompiles = bench_serve.main(
                quick=args.quick)
        emit(buf.getvalue())
        print(f"# serve speedup {serve_speedup:.2f}x vs per-request "
              f"engine loop; {serve_recompiles} post-warmup compiles "
              "(target 0)", file=sys.stderr)

    stream_speedup = stream_retraces = None
    if args.stream:
        section("streaming incremental vs cold rebuild (bench_stream)")
        from . import bench_stream
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            stream_speedup, stream_retraces = bench_stream.main(
                quick=args.quick)
        emit(buf.getvalue())
        print(f"# stream incremental-vs-cold worst gated speedup "
              f"{stream_speedup:.2f}x (target >= 5x); "
              f"{stream_retraces} post-warmup retraces (target 0)",
              file=sys.stderr)

    section("gan step cost: objective vs dense baseline (Sec 4)")
    from . import bench_gan
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        gan_speedup, gan_parity = bench_gan.main(
            batch_sizes=(512, 1024) if args.quick
            else (512, 1024, 2048))
    emit(buf.getvalue())
    print(f"# gan objective-vs-dense speedup {gan_speedup:.2f}x "
          f"(--gan target >= 2x); worst loss parity rel "
          f"{gan_parity:.3f}", file=sys.stderr)

    section("roofline (from dry-run artifacts)")
    try:
        from . import roofline
        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            roofline.main()
        emit(buf.getvalue())
    except Exception as e:  # noqa: BLE001
        emit(f"roofline/unavailable,0,reason={e!r}")

    if args.json:
        parsed = []
        for line in rows:
            parts = line.split(",", 2)
            if len(parts) == 3:
                name, us, derived = parts
                try:
                    us_val = float(us)
                except ValueError:
                    continue
                parsed.append(dict(name=name, us_per_call=us_val,
                                   derived=derived))
        artifact = dict(
            schema="bench-rows-v1",
            backend=jax.default_backend(),
            platform=platform.platform(),
            quick=bool(args.quick),
            pallas=bool(args.pallas),
            batched_speedup=float(speedup),
            rows=parsed,
        )
        if fused_speedup is not None:
            artifact["fused_speedup"] = float(fused_speedup)
        if serve_speedup is not None:
            artifact["serve_speedup"] = float(serve_speedup)
        if stream_speedup is not None:
            artifact["stream_speedup"] = float(stream_speedup)
        if tuned_ratio is not None:
            artifact["tuned_ratio"] = float(tuned_ratio)
        artifact["gan_speedup"] = float(gan_speedup)
        with open(args.json, "w") as fh:
            json.dump(artifact, fh, indent=1)
        print(f"# wrote {len(parsed)} rows to {args.json}", file=sys.stderr)

    # gate: the tier-1 perf contracts fail the process, not just the rows
    failures = []
    if speedup < 3.0:
        failures.append(f"batched speedup {speedup:.2f}x < 3x")
    if fused_speedup is not None and fused_speedup < 1.5:
        failures.append(
            f"megakernel fused-vs-unfused us/iter ratio {fused_speedup:.2f}x"
            " < 1.5x on every solver/iter shape")
    if serve_recompiles:
        failures.append(
            f"{serve_recompiles} post-warmup serving-path compiles/"
            "retraces (must be zero)")
    if stream_speedup is not None and stream_speedup < 5.0:
        failures.append(
            f"stream incremental-vs-cold speedup {stream_speedup:.2f}x "
            "< 5x on a gated shape")
    if stream_retraces:
        failures.append(
            f"{stream_retraces} post-warmup streaming-runner retraces "
            "(must be zero)")
    if args.gan and gan_speedup < 2.0:
        failures.append(
            f"GAN objective-vs-dense step speedup {gan_speedup:.2f}x < 2x")
    if tuned_ratio is not None and tuned_ratio < 1.0:
        failures.append(
            f"tuned-vs-static us/iter ratio {tuned_ratio:.2f} < 1.0 — "
            "the tuner lost to the static pick_block heuristic")
    if args.tune_expect_cached and tune_trials:
        failures.append(
            f"{tune_trials} timing trials against a supposedly warm "
            "tuning cache (must be zero)")
    failures.extend(tune_failures)
    if args.baseline:
        with open(args.baseline) as fh:
            base = json.load(fh)
        base_speedup = float(base["batched_speedup"])
        floor = 0.75 * base_speedup
        status = "PASS" if speedup >= floor else "FAIL"
        print(f"batch/baseline_gate,0,speedup={speedup:.2f};"
              f"baseline={base_speedup:.2f};floor={floor:.2f};ok={status}")
        if speedup < floor:
            failures.append(
                f"batched speedup {speedup:.2f}x regressed >25% vs "
                f"committed baseline {base_speedup:.2f}x "
                f"(floor {floor:.2f}x, {args.baseline})")
        base_fused = base.get("fused_speedup")
        if fused_speedup is not None and base_fused is not None:
            ffloor = 0.75 * float(base_fused)
            fstatus = "PASS" if fused_speedup >= ffloor else "FAIL"
            print(f"solver/fused_baseline_gate,0,"
                  f"speedup={fused_speedup:.2f};"
                  f"baseline={float(base_fused):.2f};floor={ffloor:.2f};"
                  f"ok={fstatus}")
            if fused_speedup < ffloor:
                failures.append(
                    f"megakernel speedup {fused_speedup:.2f}x regressed "
                    f">25% vs committed baseline {float(base_fused):.2f}x "
                    f"(floor {ffloor:.2f}x, {args.baseline})")
        base_gan = base.get("gan_speedup")
        if base_gan is not None:
            gfloor = 0.75 * float(base_gan)
            gstatus = "PASS" if gan_speedup >= gfloor else "FAIL"
            print(f"gan_step/baseline_gate,0,speedup={gan_speedup:.2f};"
                  f"baseline={float(base_gan):.2f};floor={gfloor:.2f};"
                  f"ok={gstatus}")
            if gan_speedup < gfloor:
                failures.append(
                    f"GAN step speedup {gan_speedup:.2f}x regressed >25% "
                    f"vs committed baseline {float(base_gan):.2f}x "
                    f"(floor {gfloor:.2f}x, {args.baseline})")
        base_stream = base.get("stream_speedup")
        if stream_speedup is not None and base_stream is not None:
            tfloor = 0.75 * float(base_stream)
            tstatus = "PASS" if stream_speedup >= tfloor else "FAIL"
            print(f"stream/baseline_gate,0,speedup={stream_speedup:.2f};"
                  f"baseline={float(base_stream):.2f};floor={tfloor:.2f};"
                  f"ok={tstatus}")
            if stream_speedup < tfloor:
                failures.append(
                    f"stream speedup {stream_speedup:.2f}x regressed >25% "
                    f"vs committed baseline {float(base_stream):.2f}x "
                    f"(floor {tfloor:.2f}x, {args.baseline})")
        base_serve = base.get("serve_speedup")
        if serve_speedup is not None and base_serve is not None:
            sfloor = 0.75 * float(base_serve)
            sstatus = "PASS" if serve_speedup >= sfloor else "FAIL"
            print(f"serve/baseline_gate,0,speedup={serve_speedup:.2f};"
                  f"baseline={float(base_serve):.2f};floor={sfloor:.2f};"
                  f"ok={sstatus}")
            if serve_speedup < sfloor:
                failures.append(
                    f"serve speedup {serve_speedup:.2f}x regressed >25% "
                    f"vs committed baseline {float(base_serve):.2f}x "
                    f"(floor {sfloor:.2f}x, {args.baseline})")
    if args.pallas and any("pallas_ok" in r and "ok=False" in r
                           for r in rows):
        failures.append("fused-plan parity check failed (batch/pallas_ok)")
    # structured-health gates: a row that reports a diverged solve or a
    # fused-vs-XLA iteration-count mismatch is a hard failure — this is
    # what keeps e.g. the Nystrom geometry rows from silently regressing
    # to diverged=True again
    bad_div = [r.split(",", 1)[0] for r in rows if "diverged=True" in r]
    if bad_div:
        failures.append("diverged=True rows: " + " ".join(bad_div))
    bad_match = [r.split(",", 1)[0] for r in rows if "match=False" in r]
    if bad_match:
        failures.append("match=False rows: " + " ".join(bad_match))
    if failures:
        print("# FAIL: " + "; ".join(failures), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
