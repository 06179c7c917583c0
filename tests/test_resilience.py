"""Resilience: failure classification, the recovery ladder, and the
fault-injection contracts.

The subsystem's promises under test:

* classification — ``classify`` reads one solve into an ``ok`` /
  ``maxed_out`` / ``diverged`` / ``poisoned_warm_start`` verdict, with
  ``-inf`` potentials on ZERO-WEIGHT atoms recognised as the legitimate
  padding contract, not poison;
* the core ladder — a scaling-domain solve that underflows at small eps
  recovers through the ``log_domain`` rung and lands within solver
  tolerance of the log-domain ground truth;
* lane isolation — a diverged lane inside a ``solve_many`` bucket (and
  inside an ``OTService`` megabatch with replicated padding) never
  perturbs its healthy siblings: their results match solo solves
  elementwise;
* warm-cache hygiene — non-finite potentials are rejected at ``store``,
  evicted at ``lookup``, and a diverged solve can never poison the next
  exact-repeat request;
* bounded-queue shedding, quarantine of repeat offenders, skewed-clock
  admission aging, the streaming cold-fallback/state-reset path, and the
  training-step admission guard.
"""
import numpy as np
import pytest

from repro.core import OTProblem, solve, solve_many
from repro.core.geometry import GaussianPointCloud
from repro.core.sinkhorn import SinkhornResult
from repro.core.spec import SolveSpec
from repro.distributed.fault_tolerance import (
    FaultToleranceConfig,
    TrainingSupervisor,
)
from repro.resilience import (
    RUNGS,
    ChaosInjector,
    ChaosSpec,
    RecoveryPolicy,
    classify,
    solve_with_recovery,
    warm_is_poisoned,
)
from repro.serving import (
    AdmissionQueue,
    OTService,
    QuarantineError,
    QueueFullError,
    WarmStartCache,
)
from repro.streaming import StreamingDistribution, StreamingSolver

EPS = 0.5
SMALL_EPS = 1e-4       # scaling-domain Gaussian features underflow here


def _problem(n, m, r=8, seed=0, eps=EPS, nan_row=None):
    rng = np.random.default_rng(seed)
    xi = np.asarray(rng.uniform(0.05, 1.05, (n, r)), np.float32)
    zeta = np.asarray(rng.uniform(0.05, 1.05, (m, r)), np.float32)
    if nan_row is not None:
        xi[nan_row] = np.nan
    a = np.asarray(rng.dirichlet(np.full(n, 2.0)), np.float32)
    b = np.asarray(rng.dirichlet(np.full(m, 2.0)), np.float32)
    return OTProblem.from_features(xi, zeta, a / a.sum(), b / b.sum(),
                                   eps=eps)


def _gauss_problem(n=14, m=12, r=8, seed=0, eps=SMALL_EPS):
    """True point clouds: recoverable small-eps failure class (the
    scaling-domain kernel underflows; log features stay finite)."""
    rng = np.random.default_rng(seed)
    x = np.asarray(rng.normal(size=(n, 2)), np.float32)
    y = np.asarray(rng.normal(size=(m, 2)), np.float32)
    anchors = np.asarray(rng.normal(size=(r, 2)), np.float32)
    geom = GaussianPointCloud.build(x, y, anchors, eps=eps)
    a = np.full(n, 1.0 / n, np.float32)
    b = np.full(m, 1.0 / m, np.float32)
    return OTProblem.from_geometry(geom, a, b)


def _result(err, cost, n_iter=7, converged=True, n=3, m=3):
    z = np.zeros(n, np.float32)
    w = np.zeros(m, np.float32)
    return SinkhornResult(u=z, v=w, f=z, g=w,
                          cost=np.float64(cost), n_iter=np.int32(n_iter),
                          marginal_err=np.float64(err),
                          converged=np.bool_(converged))


# -- classification -----------------------------------------------------------


def test_classify_verdicts():
    ok = classify(_result(1e-8, 0.3, converged=True))
    assert ok.verdict == "ok" and ok.ok and ok.finite and not ok.failed
    assert "ok" in ok.describe()

    maxed = classify(_result(1e-3, 0.3, converged=False))
    assert maxed.verdict == "maxed_out"
    assert maxed.finite and not maxed.ok and not maxed.failed

    div = classify(_result(np.nan, np.nan, converged=False))
    assert div.verdict == "diverged" and div.failed and not div.finite

    # same diagnostics, but the warm start handed in was already corrupt
    f0 = np.array([0.0, np.nan, 0.0])
    poisoned = classify(_result(np.nan, np.nan, converged=False),
                        f_init=f0, g_init=np.zeros(3))
    assert poisoned.verdict == "poisoned_warm_start" and poisoned.failed


def test_warm_is_poisoned_weight_masking():
    assert not warm_is_poisoned(None, None)
    assert not warm_is_poisoned(np.zeros(3), np.zeros(3))
    assert warm_is_poisoned(np.array([0.0, np.nan]), None)
    assert warm_is_poisoned(None, np.array([np.inf, 0.0]))
    # -inf without weights: conservative poison
    neg = np.array([0.0, -np.inf, 0.0])
    assert warm_is_poisoned(neg, None)
    # -inf on a ZERO-weight atom is the padding contract, not poison
    a_dead = np.array([0.5, 0.0, 0.5])
    assert not warm_is_poisoned(neg, None, a=a_dead)
    # ... but on a mass-carrying atom it is poison
    a_live = np.array([0.3, 0.4, 0.3])
    assert warm_is_poisoned(neg, None, a=a_live)


def test_result_health_property_end_to_end():
    good = solve(_problem(10, 9, seed=1), method="factored", tol=1e-6,
                 max_iter=500)
    assert good.health.ok

    bad = solve(_problem(10, 9, seed=1, nan_row=2), method="factored",
                tol=1e-6, max_iter=50)
    assert bad.health.verdict == "diverged" and bad.health.failed


# -- policy validation --------------------------------------------------------


def test_recovery_policy_validation():
    RecoveryPolicy()                       # defaults are legal
    with pytest.raises(ValueError, match="unknown recovery rungs"):
        RecoveryPolicy(rungs=("log_domain", "reboot"))
    with pytest.raises(ValueError, match="duplicate"):
        RecoveryPolicy(rungs=("log_domain", "log_domain"))
    with pytest.raises(ValueError, match="max_attempts"):
        RecoveryPolicy(max_attempts=0)
    with pytest.raises(ValueError, match="eps_scale"):
        RecoveryPolicy(eps_scale=1.0)
    with pytest.raises(ValueError, match="deadline_s"):
        RecoveryPolicy(deadline_s=0.0)
    with pytest.raises(ValueError, match="unknown verdicts"):
        RecoveryPolicy(accept=("ok", "fine"))
    with pytest.raises(ValueError, match="at least one"):
        RecoveryPolicy(accept=())


def test_ordered_rungs_poisoned_pulls_cold_restart_first():
    pol = RecoveryPolicy()
    assert pol.ordered_rungs("diverged") == RUNGS
    reordered = pol.ordered_rungs("poisoned_warm_start")
    assert reordered[0] == "cold_restart"
    assert set(reordered) == set(RUNGS)
    # a ladder without cold_restart keeps its order
    pol2 = RecoveryPolicy(rungs=("log_domain",))
    assert pol2.ordered_rungs("poisoned_warm_start") == ("log_domain",)


def test_spec_recovery_type_checked():
    p = _problem(8, 8)
    with pytest.raises(TypeError, match="RecoveryPolicy"):
        SolveSpec.from_problem(p, recovery="retry-hard")


# -- the core ladder ----------------------------------------------------------


def test_ladder_recovers_small_eps_underflow():
    p = _gauss_problem(seed=3)
    spec = SolveSpec.from_problem(p, method="factored", tol=1e-4,
                                  max_iter=300,
                                  recovery=RecoveryPolicy())
    # base configuration genuinely fails ...
    base = solve(spec.replace(recovery=None))
    assert base.health.failed

    rec = solve_with_recovery(spec)
    assert rec.health.finite and rec.recovered
    assert rec.attempts >= 2 and rec.rungs[0] == "log_domain"
    assert rec.history[0][0] == "initial"
    assert rec.history[0][1].failed
    # ... and the recovered answer matches the log-domain ground truth
    ref = solve(p, method="log_factored", tol=1e-4, max_iter=300)
    assert abs(float(rec.result.cost) - float(ref.cost)) <= \
        1e-6 + 1e-5 * abs(float(ref.cost))

    # solve(spec) with recovery attached routes through the same ladder
    auto = solve(spec)
    assert auto.health.finite
    np.testing.assert_allclose(np.asarray(auto.f),
                               np.asarray(rec.result.f),
                               rtol=1e-6, atol=1e-6)


def test_ladder_healthy_solve_is_single_attempt():
    spec = SolveSpec.from_problem(_problem(10, 9, seed=5),
                                  method="factored", tol=1e-6,
                                  max_iter=500, recovery=RecoveryPolicy())
    rec = solve_with_recovery(spec)
    assert rec.health.ok and rec.attempts == 1
    assert rec.rungs == () and not rec.recovered


def test_ladder_exhausts_on_unrecoverable_input():
    # NaN features defeat every rung: the ladder must terminate with a
    # failed verdict inside its attempt budget, not loop or raise
    p = _problem(10, 9, seed=7, nan_row=1)
    spec = SolveSpec.from_problem(
        p, method="factored", tol=1e-6, max_iter=50,
        recovery=RecoveryPolicy(max_attempts=3))
    rec = solve_with_recovery(spec)
    assert rec.health.failed and not rec.recovered
    assert rec.attempts <= 3
    assert all(h.failed for _, h in rec.history)


# -- lane isolation (satellite: diverged lane never poisons siblings) ---------


def test_solve_many_diverged_lane_sibling_parity():
    healthy = [_gauss_problem(seed=s, eps=EPS) for s in (1, 2)]
    bad = _problem(14, 12, seed=9, eps=EPS, nan_row=0)
    alt = _problem(14, 12, seed=10, eps=EPS)
    mk = lambda p: SolveSpec.from_problem(p, method="factored", tol=1e-6,
                                          max_iter=400,
                                          recovery=RecoveryPolicy())

    batched = solve_many([mk(healthy[0]), mk(bad), mk(healthy[1])])
    # swap the bad lane for a healthy one, same batch size/positions: the
    # siblings must be BITWISE identical — the NaN lane shared their
    # vmapped loop but never touched them (converged lanes are frozen)
    clean = solve_many([mk(healthy[0]), mk(alt), mk(healthy[1])])
    for i in (0, 2):
        assert np.array_equal(np.asarray(batched[i].f),
                              np.asarray(clean[i].f))
        assert np.array_equal(np.asarray(batched[i].g),
                              np.asarray(clean[i].g))
        assert batched[i].health.ok
    # ... and match solo (batch-1) solves to float32 matmul roundoff
    solo = [solve_many([mk(p)])[0] for p in healthy]
    for got, ref in zip((batched[0], batched[2]), solo):
        np.testing.assert_allclose(np.asarray(got.f), np.asarray(ref.f),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(got.g), np.asarray(ref.g),
                                   rtol=1e-5, atol=1e-5)
    # the bad lane climbed the ladder individually and stayed failed
    # (NaN input is unrecoverable) without raising
    assert batched[1].health.failed


def test_service_bad_lane_isolated_and_recovered():
    svc = OTService(eps=SMALL_EPS, method="factored", tol=1e-4,
                    max_iter=300, max_batch=4, max_wait=0.0,
                    recovery=RecoveryPolicy(), quarantine_after=3)
    healthy = [_problem(14, 12, seed=s, eps=SMALL_EPS) for s in (1, 2)]
    gauss = _gauss_problem(seed=4)               # recoverable divergence
    nan = _problem(14, 12, seed=9, eps=SMALL_EPS, nan_row=0)

    tickets = [svc.submit(p) for p in (healthy[0], gauss, nan, healthy[1])]
    svc.drain()
    t_h0, t_gauss, t_nan, t_h1 = tickets
    assert all(t.done for t in tickets)

    # healthy lanes: elementwise parity vs a solo (batch-1) service solve
    solo = [OTService(eps=SMALL_EPS, method="factored", tol=1e-4,
                      max_iter=300, max_batch=1).solve_many([p])[0]
            for p in healthy]
    for t, ref in zip((t_h0, t_h1), solo):
        assert t.health.finite and t.refusal is None
        np.testing.assert_allclose(np.asarray(t.result.f),
                                   np.asarray(ref.f),
                                   rtol=1e-6, atol=1e-7)
        np.testing.assert_allclose(np.asarray(t.result.g),
                                   np.asarray(ref.g),
                                   rtol=1e-6, atol=1e-7)

    # the underflow lane climbed the ladder: finite, via log_domain
    assert t_gauss.health is not None and t_gauss.health.finite
    assert t_gauss.attempts > 1 and "log_domain" in t_gauss.rungs
    ref_g = solve(gauss, method="log_factored", tol=1e-4, max_iter=300)
    assert abs(float(t_gauss.result.cost) - float(ref_g.cost)) <= \
        1e-6 + 1e-4 * abs(float(ref_g.cost))

    # the NaN lane exhausted the ladder: structured refusal, no NaN served
    assert t_nan.result is None and t_nan.refusal is not None
    assert t_nan.refusal.reason == "recovery_exhausted"
    assert t_nan.refusal.health is not None and t_nan.refusal.health.failed

    s = svc.stats()
    assert s["recovery"]["recovered"] >= 1
    assert s["recovery"]["refused"] == 1
    assert s["recovery"]["rung_hist"].get("log_domain", 0) >= 1
    assert s["health"].get("diverged", 0) >= 1


@pytest.mark.parametrize("where", ["batched", "ladder"])
def test_service_runner_build_errors_propagate(where, monkeypatch):
    """Recovery absorbs faults of a compiled runner, never an error from
    building one: a kernel the compiler refuses must fail the request
    loudly, not come back as a rescued solve on a degraded rung."""
    from repro.serving.runner_cache import BucketRunner

    svc = OTService(eps=EPS, method="factored", tol=1e-4, max_iter=40,
                    max_batch=2, max_wait=0.0, recovery=RecoveryPolicy())
    if where == "ladder":
        svc.warmup([(14, 12, 8)], batches=[1])  # only rung runners build
    warmup = BucketRunner.warmup

    def refuse(self):
        raise RuntimeError("Mosaic failed to compile the kernel")

    monkeypatch.setattr(BucketRunner, "warmup", refuse)
    if where == "ladder":
        # a runtime fault in the batched run sends the request down the
        # ladder, whose rung runners are built on demand
        monkeypatch.setattr(svc, "chaos_hook", ChaosInjector(ChaosSpec(
            runner_fault_frac=1.0, nan_feature_frac=0.0,
            inf_feature_frac=0.0, nan_weight_frac=0.0)).fault_hook())
    svc.submit(_problem(14, 12, seed=3))
    with pytest.raises(RuntimeError, match="failed to compile"):
        svc.drain()
    assert svc.runner_faults == (1 if where == "ladder" else 0)
    assert svc.recovered == 0
    # with runner builds restored, a runtime fault is still absorbed
    monkeypatch.setattr(BucketRunner, "warmup", warmup)
    if where == "ladder":
        t = svc.submit(_problem(14, 12, seed=3))
        svc.drain()
        assert t.done and svc.runner_faults == 2 and svc.recovered == 1


# -- warm-start cache hygiene (satellite: cache poisoning) --------------------


def test_warmstart_rejects_poison_at_store():
    cache = WarmStartCache()
    a = np.array([0.5, 0.5], np.float32)
    b = np.array([0.25, 0.75], np.float32)
    sk, fk = b"s", b"f"
    assert not cache.store(sk, fk, np.array([np.nan, 0.0]), np.zeros(2),
                           a, b)
    assert len(cache) == 0 and cache.snapshot()["poisoned_rejects"] == 1

    # -inf on a dead atom is the padding contract: accepted, sanitized
    a_dead = np.array([1.0, 0.0], np.float32)
    assert cache.store(sk, fk, np.array([0.1, -np.inf]), np.zeros(2),
                       a_dead, b)
    hit = cache.lookup(sk, fk)
    assert hit is not None and np.isfinite(np.asarray(hit.f)).all()


def test_warmstart_evicts_poison_at_lookup():
    cache = WarmStartCache()
    sk, fk = b"s", b"f"
    cache.store(sk, fk, np.array([np.nan, 1.0]), np.zeros(2),
                validate=False)         # simulated corrupted snapshot
    assert len(cache) == 1
    assert cache.lookup(sk, fk) is None
    assert len(cache) == 0
    assert cache.snapshot()["poisoned_evictions"] == 1


def test_service_diverged_solve_never_poisons_next_request():
    # regression: pre-fix, a diverged solve stored NaN potentials and the
    # exact repeat warm-started from them
    svc = OTService(eps=EPS, method="factored", tol=1e-6, max_iter=50,
                    max_batch=1)
    bad = _problem(10, 9, seed=11, nan_row=3)
    t1 = svc.submit(bad)
    svc.drain()
    assert t1.health.failed          # served as-is: no recovery configured
    assert svc.warm.snapshot()["poisoned_rejects"] >= 1

    t2 = svc.submit(bad)             # exact repeat must cold-solve
    svc.drain()
    assert not t2.warm_hit


# -- admission shedding (satellite: bounded queue depth) ----------------------


def test_admission_queue_sheds_at_max_depth():
    with pytest.raises(ValueError, match="max_depth"):
        AdmissionQueue(max_depth=0)
    q = AdmissionQueue(max_batch=8, max_wait=10.0, max_depth=2)
    q.add("cell", "r0", now=0.0)
    q.add("cell", "r1", now=0.0)
    assert q.full
    with pytest.raises(QueueFullError):
        q.add("cell", "r2", now=0.0)
    assert q.shed == 1 and len(q) == 2
    # draining restores capacity
    q.pop_due(now=0.0, force=True)
    q.add("cell", "r3", now=0.0)
    assert q.shed == 1 and len(q) == 1


def test_admission_survives_clock_skew():
    # a skewed `now` can run BACKWARDS between reads; aging must neither
    # crash nor wedge the group
    q = AdmissionQueue(max_batch=4, max_wait=0.5)
    q.add("cell", "r0", now=10.0)
    assert q.pop_due(now=9.7) == []          # clock went backwards
    assert q.next_deadline() == pytest.approx(10.5)
    due = q.pop_due(now=10.6)                # recovered past the deadline
    assert [k for k, _ in due] == ["cell"]
    inj = ChaosInjector(ChaosSpec(seed=1, clock_skew_s=0.01))
    base = [100.0]
    skewed = inj.skewed(lambda: base[0])
    reads = [skewed() for _ in range(32)]
    assert all(abs(r - 100.0) <= 0.01 for r in reads)
    assert inj.clock_reads == 32


# -- quarantine ---------------------------------------------------------------


def test_service_quarantines_repeat_offenders():
    svc = OTService(eps=EPS, method="factored", tol=1e-4, max_iter=40,
                    max_batch=1, quarantine_after=2,
                    recovery=RecoveryPolicy(
                        rungs=("log_domain", "cold_restart"),
                        max_attempts=2))
    bad = _problem(10, 9, seed=13, nan_row=2)
    for _ in range(2):
        t = svc.submit(bad)
        svc.drain()
        assert t.refusal is not None
    with pytest.raises(QuarantineError):
        svc.submit(bad)
    s = svc.stats()
    assert s["recovery"]["quarantine_rejects"] == 1
    assert s["recovery"]["quarantined"] == 1
    # a DIFFERENT request is unaffected
    t_ok = svc.submit(_problem(10, 9, seed=14))
    svc.drain()
    assert t_ok.health.ok


# -- chaos injector determinism -----------------------------------------------


def test_chaos_spec_validation_and_determinism():
    with pytest.raises(ValueError, match="partition"):
        ChaosSpec(nan_feature_frac=0.8, inf_feature_frac=0.3)
    s = ChaosSpec(seed=5, nan_feature_frac=0.25, inf_feature_frac=0.125,
                  nan_weight_frac=0.125)
    assigned = ChaosInjector(s).assign_faults(16)
    assert assigned == ChaosInjector(s).assign_faults(16)   # replayable
    assert assigned.count("nan_feature") == 4
    assert assigned.count("inf_feature") == 2
    assert assigned.count("nan_weight") == 2
    assert assigned.count("") == 8

    inj = ChaosInjector(s)
    xi = np.ones((6, 3), np.float32)
    out = inj.corrupt_features(xi, "nan_feature")
    assert np.isnan(out).any() and np.isfinite(xi).all()    # copy, not alias
    assert int(np.isnan(out).any(axis=1).sum()) == 1        # one row
    w = inj.corrupt_weights(np.ones(5, np.float32))
    assert int(np.isnan(w).sum()) == 1
    stats = inj.stats()
    assert stats["nan_feature"] == 1 and stats["inf_feature"] == 0
    assert stats["nan_weight"] == 1 and stats["runner_faults"] == 0


def test_chaos_fault_hook_raises_and_counts():
    inj = ChaosInjector(ChaosSpec(seed=0, runner_fault_frac=1.0,
                                  nan_feature_frac=0.0,
                                  inf_feature_frac=0.0,
                                  nan_weight_frac=0.0))
    hook = inj.fault_hook()
    with pytest.raises(RuntimeError, match="chaos"):
        hook((16, 16, 8), 2)
    assert inj.runner_faults == 1


# -- streaming resilience -----------------------------------------------------


def _streams(n=10, m=9, r=6, seed=21):
    rng = np.random.default_rng(seed)
    feats = lambda k: np.asarray(rng.uniform(0.05, 1.05, (k, r)), np.float32)
    w = lambda k: np.asarray(rng.uniform(0.5, 1.5, k), np.float32)
    dx = StreamingDistribution.from_features(
        [f"x{i}" for i in range(n)], feats(n), w(n), eps=EPS, page_size=8)
    dy = StreamingDistribution.from_features(
        [f"y{i}" for i in range(m)], feats(m), w(m), eps=EPS, page_size=8)
    return dx, dy


def test_streaming_warm_reset_and_cold_fallback():
    solver = StreamingSolver(method="scaling", tol=1e-6, max_iter=500)
    pair = solver.register("p", *_streams())
    solver.warmup(pair)
    res = solver.re_solve(pair)
    assert pair.last_health.finite and np.isfinite(float(res.cost))
    cost_good = float(res.cost)

    # NaN entries in the persisted potentials: sanitized BEFORE the solve
    pair.f = np.where(np.arange(pair.f.shape[0]) % 3 == 0, np.nan,
                      pair.f).astype(np.float32)
    res = solver.re_solve(pair)
    assert solver.warm_resets > 0 and pair.last_health.finite
    assert abs(float(res.cost) - cost_good) <= 1e-5 * (1 + abs(cost_good))

    # finite-but-absurd potentials overflow the scaling warm start: the
    # retry reruns COLD through the same runner and succeeds
    traces0 = solver.traces
    pair.f = np.full(pair.f.shape, 1e30, np.float32)
    res = solver.re_solve(pair)
    assert solver.cold_fallbacks == 1 and pair.last_health.finite
    assert solver.traces == traces0          # no retrace for the fallback
    assert abs(float(res.cost) - cost_good) <= 1e-5 * (1 + abs(cost_good))


def test_streaming_store_rejects_nonfinite_rows():
    # NaN slips past a bare `<= 0` check (NaN <= 0 is False): the store
    # must reject non-finite rows at its only write boundary, because a
    # NaN row in a LIVE page cannot be scrubbed by weight masking
    dx, _ = _streams()
    for bad in (np.nan, np.inf):
        with pytest.raises(ValueError, match="finite"):
            dx.add(["poison"], feats=np.full((1, 6), bad, np.float32),
                   weights=np.ones(1, np.float32))
    with pytest.raises(ValueError, match="finite"):
        dx.add(["poison"], feats=np.ones((1, 6), np.float32),
               weights=np.full(1, np.nan, np.float32))


def test_streaming_terminal_divergence_resets_state():
    solver = StreamingSolver(method="scaling", tol=1e-6, max_iter=100)
    pair = solver.register("p", *_streams(seed=22))
    solver.warmup(pair)
    solver.re_solve(pair)
    assert pair.f is not None

    # a denormal feature row underflows its kernel contraction to exactly
    # 0 (a/0 = inf on the live atom): warm AND cold solves fail, so the
    # persisted potentials must drop — the poison dies with this solve
    pair.x.add(["poison"], feats=np.full((1, 6), 1e-44, np.float32),
               weights=np.ones(1, np.float32))
    solver.re_solve(pair)
    assert pair.last_health.failed
    assert solver.diverged == 1 and solver.state_resets == 1
    assert solver.cold_fallbacks == 1
    assert pair.f is None and pair.g is None

    # removing the poison heals: the stale row is now a DEAD slot, which
    # the masked scaling step pins to 0 (never 0/0), and the next solve
    # cold-starts healthy
    pair.x.remove(["poison"])
    res = solver.re_solve(pair)
    assert pair.last_health.finite and np.isfinite(float(res.cost))
    assert pair.f is not None
    for key in ("diverged", "cold_fallbacks", "state_resets",
                "warm_resets"):
        assert key in solver.stats()


# -- training-step admission guard --------------------------------------------


def test_supervisor_admit_step_guard():
    sup = TrainingSupervisor(None, FaultToleranceConfig(
        max_consecutive_skips=2))
    assert sup.admit_step({"loss": 1.25, "ot": 0.3, "tag": "warmup"})
    assert sup.skipped_steps == 0

    assert not sup.admit_step({"loss": 1.2, "ot": float("nan")})
    assert not sup.admit_step({"loss": float("inf"), "ot": 0.2})
    assert sup.skipped_steps == 2 and sup.consecutive_skips == 2

    # a finite step resets the streak (but not the total)
    assert sup.admit_step({"loss": 1.1, "ot": 0.2})
    assert sup.consecutive_skips == 0 and sup.skipped_steps == 2

    # a streak past the bound aborts instead of spinning forever
    assert not sup.admit_step({"loss": float("nan")})
    assert not sup.admit_step({"loss": float("nan")})
    with pytest.raises(RuntimeError, match="consecutive"):
        sup.admit_step({"loss": float("nan")})
