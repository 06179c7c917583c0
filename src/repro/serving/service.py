"""OT-as-a-service: a persistent request-driven front end for the batched
solver engine.

The pieces (each its own module, composable and unit-testable):

* :class:`~repro.serving.runner_cache.RunnerCache` — pre-planned,
  warm-up-executed jitted runners per ``(OTBatchShape, B)`` bucket cell:
  steady-state requests never trace or compile.
* :class:`~repro.serving.admission.AdmissionQueue` — continuous batching
  of ragged requests into bucket-padded megabatches under a
  max-batch/max-wait policy.
* :class:`~repro.serving.warmstart.WarmStartCache` — fingerprinted
  potentials re-served through the engine's ``f_init``/``g_init`` path
  for repeat (exact) and near-repeat (good-init) pairs.

Usage::

    svc = OTService(eps=0.05, method="log_factored", max_batch=8,
                    max_wait=0.002)
    svc.warmup([(200, 150, 64)])          # pre-plan the expected buckets
    t = svc.submit(problem)               # -> Ticket
    svc.pump()                           # dispatch due megabatches
    svc.drain()                          # flush everything pending
    t.result                             # per-request unpadded SinkhornResult

``submit``/``pump``/``drain`` are synchronous and single-threaded by
design: the event loop (a driver script, an RPC handler, the open-loop
benchmark) owns scheduling, the service owns batching and caching. All
time is injected (``clock=``), so tests drive the max-wait policy with a
fake clock.
"""
from __future__ import annotations

import dataclasses
import time
from collections import Counter, OrderedDict
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np

from ..configs.shapes import OTBatchShape, ot_batch_bucket
from ..core.api import (
    BatchedSinkhorn,
    OTProblem,
    engine_cache_info,
    get_engine,
)
from ..core.sinkhorn import SinkhornResult
from ..resilience.health import SolveHealth, classify
from ..resilience.ladder import LOG_METHODS, LOG_TWIN
from ..resilience.policy import RecoveryPolicy
from .admission import AdmissionQueue, QueueFullError
from .runner_cache import RunnerCache
from .warmstart import WarmStartCache

__all__ = ["Ticket", "OTService", "Refusal", "QuarantineError",
           "QueueFullError"]


_RESULT_FIELDS = ("u", "v", "f", "g", "cost", "n_iter", "marginal_err",
                  "converged")


class _RunnerFault(RuntimeError):
    """An exception raised while a COMPILED runner executed (or by the
    chaos hook just before it) — the only kind recovery absorbs. Errors
    from building, lowering or compiling a runner are never wrapped, so
    they propagate instead of turning into a recovered request."""


def _execute(runner, arrays, hook: Optional[Callable] = None,
             cell=None) -> Dict[str, np.ndarray]:
    """Run one compiled megabatch and pull it to the host in one transfer;
    failures surface as :class:`_RunnerFault`."""
    try:
        if hook is not None:
            hook(*cell)
        res = runner.run(*arrays)
        return {k: np.asarray(getattr(res, k)) for k in _RESULT_FIELDS}
    except Exception as exc:
        raise _RunnerFault(f"{type(exc).__name__}: {exc}") from exc


class QuarantineError(RuntimeError):
    """Submit-time refusal of a quarantined repeat-offender fingerprint
    (a request that has already exhausted the recovery ladder
    ``quarantine_after`` times — re-admitting it would burn a full ladder
    of solves for a known-unsolvable input)."""


@dataclasses.dataclass(frozen=True)
class Refusal:
    """Structured terminal refusal attached to a :class:`Ticket` whose
    request could not be recovered: the caller gets a reason and the last
    attempt's health instead of a NaN cost."""

    reason: str                      # "recovery_exhausted" | "runner_fault"
    detail: str
    health: Optional[SolveHealth]    # last attempt's verdict (if any ran)


# -- host-side padding/unpadding ---------------------------------------------
#
# The dispatch path deliberately stays in NUMPY until the single jitted
# runner call: every jnp slice/concat on a new shape eagerly compiles a
# tiny XLA executable (~tens of ms on CPU the first time) and pays a
# dispatch round trip every time after — measured to dominate per-request
# latency when the glue ran through jnp. Host-side padding is exact (same
# replicate/zero-fill semantics as core.api._pad_rows) and costs
# microseconds.


def _pad_np(arr, n_pad: int, *, replicate: bool,
            fill: float = 0.0) -> np.ndarray:
    x = np.asarray(arr)
    pad = n_pad - x.shape[0]
    if pad <= 0:
        return x
    if replicate:
        tail = np.broadcast_to(x[-1:], (pad,) + x.shape[1:])
    else:
        tail = np.full((pad,) + x.shape[1:], fill, x.dtype)
    return np.concatenate([x, tail], axis=0)


def _pad_kernel_np(ka: np.ndarray, kb: np.ndarray, shape: OTBatchShape,
                   quadratic: bool) -> Tuple[np.ndarray, np.ndarray]:
    if quadratic:
        ka = _pad_np(ka, shape.n_pad, replicate=True)
        ka = _pad_np(ka.T, shape.m_pad, replicate=True).T
        return ka, ka
    return (_pad_np(ka, shape.n_pad, replicate=True),
            _pad_np(kb, shape.m_pad, replicate=True))


def _unpad_np(host: Dict[str, np.ndarray], j: int, n: int,
              m: int) -> SinkhornResult:
    """Slice request ``j`` out of a batch result already pulled to host."""
    return SinkhornResult(
        u=host["u"][j, :n], v=host["v"][j, :m],
        f=host["f"][j, :n], g=host["g"][j, :m],
        cost=host["cost"][j], n_iter=host["n_iter"][j],
        marginal_err=host["marginal_err"][j],
        converged=host["converged"][j],
    )


class Ticket:
    """Handle for one submitted request; filled in by the dispatch path.

    A ticket always terminates in exactly one of two states: ``result``
    (a finite-or-classified solve — read ``health`` for the verdict) or
    ``refusal`` (the structured no-NaN failure contract when the recovery
    ladder is exhausted). ``attempts``/``rungs`` record the recovery work
    the request consumed."""

    __slots__ = ("seq", "t_submit", "t_done", "result", "warm_hit",
                 "warm_exact", "health", "refusal", "attempts", "rungs")

    def __init__(self, seq: int, t_submit: float):
        self.seq = seq
        self.t_submit = t_submit
        self.t_done: Optional[float] = None
        self.result: Optional[SinkhornResult] = None
        self.warm_hit = False
        self.warm_exact = False
        self.health: Optional[SolveHealth] = None
        self.refusal: Optional[Refusal] = None
        self.attempts = 1            # solve attempts consumed (>= 1 once run)
        self.rungs: Tuple[str, ...] = ()

    @property
    def done(self) -> bool:
        return self.result is not None or self.refusal is not None

    @property
    def latency(self) -> float:
        if self.t_done is None:
            raise RuntimeError("request not served yet")
        return self.t_done - self.t_submit


@dataclasses.dataclass
class _Admitted:
    """One admitted request: host-side kernel data + warm-start state +
    its ticket."""

    ticket: Ticket
    ka: np.ndarray
    kb: np.ndarray
    a: np.ndarray
    b: np.ndarray
    n: int
    m: int
    support_key: bytes
    full_key: bytes
    f0: Optional[np.ndarray]      # warm potentials (unpadded) or None
    g0: Optional[np.ndarray]
    problem: Optional[OTProblem] = None   # kept only when recovery may
    # need to re-derive kernel data under a different method/eps


class OTService:
    """Persistent OT solver service over the batched vmapped engine.

    Solver knobs mirror :class:`~repro.core.api.BatchedSinkhorn` (one
    service per solver configuration; the engine itself comes from the
    bounded :func:`~repro.core.api.get_engine` LRU so service and
    ``solve_many`` callers share executables and accounting). Serving
    knobs:

    ``max_batch``/``max_wait``
        admission policy (see :class:`AdmissionQueue`). Megabatches are
        additionally padded UP to power-of-two batch buckets
        (``ot_batch_bucket``) by replicating a real request lane — exact,
        the duplicate lanes are discarded — so the number of compiled
        runners stays at O(buckets x log max_batch).
    ``runner_capacity``
        LRU cap on live compiled runners.
    ``warm_capacity``/``warm_quant``/``warm_starts``
        warm-start cache size, fingerprint quantization, and a master
        switch (off = every request cold-starts; the A/B knob the
        benchmark uses).
    ``clock``
        time source (injectable for tests; defaults to
        ``time.monotonic``).

    Resilience knobs (all off by default — the happy path is unchanged):

    ``recovery``
        a :class:`~repro.resilience.policy.RecoveryPolicy`. When set,
        every dispatched lane is health-classified and failed requests
        climb the recovery ladder through PRE-PLANNED batch-1 rung
        runners (one small ``RunnerCache`` per cumulative rung
        configuration — retries never trigger a retrace storm; call
        :meth:`warmup_recovery` alongside :meth:`warmup` to pay all rung
        compiles up front). A request that exhausts the ladder gets a
        structured ``Refusal``, never a NaN cost.
    ``max_depth``
        admission-queue depth bound; ``submit`` raises
        :class:`QueueFullError` (load shedding) past it.
    ``quarantine_after``
        fingerprints that exhaust the ladder this many times are
        quarantined: later submits raise :class:`QuarantineError`
        instead of burning another full ladder.
    ``chaos_hook``
        ``hook(shape, batch)`` called before every main-path runner
        dispatch — the fault-injection seam
        (:meth:`repro.resilience.chaos.ChaosInjector.fault_hook`).
        Exceptions it raises are handled exactly like runner faults.
    """

    def __init__(
        self,
        *,
        eps: float,
        method: str = "log_factored",
        tol: float = 1e-6,
        max_iter: int = 2000,
        momentum: float = 1.0,
        use_pallas: Optional[bool] = None,
        inner_steps: Optional[int] = None,
        check_every: Optional[int] = None,
        precision: str = "highest",
        max_batch: int = 8,
        max_wait: float = 0.005,
        runner_capacity: int = 32,
        warm_capacity: int = 1024,
        warm_quant: float = 1e-6,
        warm_starts: bool = True,
        clock: Callable[[], float] = time.monotonic,
        recovery: Optional[RecoveryPolicy] = None,
        max_depth: Optional[int] = None,
        quarantine_after: int = 3,
        quarantine_capacity: int = 1024,
        chaos_hook: Optional[Callable[[OTBatchShape, int], None]] = None,
    ):
        self.engine = get_engine(
            eps=eps, method=method, tol=tol, max_iter=max_iter,
            momentum=momentum, use_pallas=use_pallas,
            inner_steps=inner_steps, check_every=check_every,
            precision=precision,
        )
        self.clock = clock
        self.max_batch = max_batch
        self.runners = RunnerCache(self.engine, capacity=runner_capacity,
                                   max_batch=max_batch)
        self.queue: AdmissionQueue[_Admitted] = AdmissionQueue(
            max_batch=max_batch, max_wait=max_wait, max_depth=max_depth)
        self.warm = WarmStartCache(capacity=warm_capacity, quant=warm_quant)
        self.warm_starts = warm_starts
        # -- resilience state ------------------------------------------------
        if recovery is not None and not isinstance(recovery, RecoveryPolicy):
            raise TypeError(
                f"recovery must be a RecoveryPolicy, got {type(recovery)}")
        if quarantine_after < 1:
            raise ValueError(
                f"quarantine_after must be >= 1, got {quarantine_after}")
        self.recovery = recovery
        self.quarantine_after = quarantine_after
        self.quarantine_capacity = quarantine_capacity
        self.chaos_hook = chaos_hook
        # full_key -> count of ladder exhaustions (bounded LRU)
        self._quarantine: "OrderedDict[bytes, int]" = OrderedDict()
        # cumulative rung config -> batch-1 RunnerCache (engines built
        # DIRECTLY, not through get_engine: recovery traffic must not
        # churn the global engine LRU the happy path lives in)
        self._rung_caches: Dict[Tuple, RunnerCache] = {}
        # served-request accounting (feeds stats() and the benchmark)
        self.served = 0
        self.batches = 0
        self.iters_warm = 0          # total solver iterations, warm-hit reqs
        self.iters_cold = 0
        self.served_warm = 0
        self.served_cold = 0
        # resilience accounting
        self.recovered = 0           # failed requests the ladder rescued
        self.refused = 0             # ladder exhausted -> structured Refusal
        self.runner_faults = 0       # runner/chaos exceptions absorbed
        self.quarantine_rejects = 0  # submits refused at quarantine
        self.recovery_attempts = 0   # total extra solves the ladder ran
        self.rung_hist: Counter = Counter()    # winning rung -> count
        self.health_hist: Counter = Counter()  # first-attempt verdicts

    # -- request path --------------------------------------------------------

    def submit(self, problem: Union[OTProblem, "SolveSpec"],
               now: Optional[float] = None) -> Ticket:
        """Admit one request: derive its kernel data and bucket cell, look
        up a warm start, enqueue. Returns the request's :class:`Ticket`
        (filled when a ``pump``/``drain`` dispatches its megabatch).

        Accepts a :class:`~repro.core.spec.SolveSpec` (the unified
        record): its geometry/weights become the request and its solver-
        facing fields are VALIDATED against this service's engine — a
        spec asking for a different eps/tol/max_iter/momentum than the
        service was built with is an error, not a silent reconfigure
        (services are per-configuration; the spec's execution policy and
        method are the service's to choose)."""
        from ..core.spec import SolveSpec
        if isinstance(problem, SolveSpec):
            spec = problem
            e = self.engine
            mismatches = [
                f"{name}: spec={got} != service={want}"
                for name, got, want in (
                    ("eps", float(spec.eps), float(e.eps)),
                    ("tol", float(spec.tol), float(e.tol)),
                    ("max_iter", int(spec.max_iter), int(e.max_iter)),
                    ("momentum", float(spec.momentum), float(e.momentum)),
                )
                if got != want
            ]
            if spec.schedule is not None:
                mismatches.append("schedule: serving solves are "
                                  "single-stage (no eps annealing)")
            if mismatches:
                raise ValueError(
                    "SolveSpec incompatible with this service's engine "
                    "(run one service per configuration): "
                    + "; ".join(mismatches))
            problem = spec.problem()
        if float(problem.eps) != float(self.engine.eps):
            raise ValueError(
                f"request declares eps={problem.eps} but this service "
                f"solves at eps={self.engine.eps}; run one service per eps"
            )
        now = self.clock() if now is None else now
        ticket = Ticket(self.queue.admitted, now)
        ka, kb = self.engine.kernel_data(problem)
        shape = self.engine.batch_shape(ka, kb)
        # everything downstream of here is host-side numpy (see the
        # module note above _pad_np); float32 is the serving dtype — the
        # runners are compiled for it, so admitting a float64 request
        # must not retrace them
        ka = np.asarray(ka, np.float32)
        kb = np.asarray(kb, np.float32)
        a = np.asarray(problem.a, np.float32)
        b = np.asarray(problem.b, np.float32)
        f0 = g0 = None
        support_key = full_key = b""
        if self.warm_starts or self.recovery is not None:
            # recovery needs the fingerprint too (quarantine is keyed on
            # it), so compute keys even when warm starts are disabled
            support_key, full_key = self.warm.keys_for(ka, kb, a, b)
        if self.recovery is not None:
            count = self._quarantine.get(full_key, 0)
            if count >= self.quarantine_after:
                self._quarantine.move_to_end(full_key)
                self.quarantine_rejects += 1
                raise QuarantineError(
                    f"request fingerprint exhausted the recovery ladder "
                    f"{count}x and is quarantined (quarantine_after="
                    f"{self.quarantine_after})")
        if self.warm_starts:
            hit = self.warm.lookup(support_key, full_key)
            if hit is not None:
                f0, g0 = hit.f, hit.g
                ticket.warm_hit = True
                ticket.warm_exact = hit.exact
        adm = _Admitted(
            ticket=ticket, ka=ka, kb=kb, a=a, b=b,
            n=a.shape[0], m=b.shape[0],
            support_key=support_key, full_key=full_key, f0=f0, g0=g0,
            problem=problem if self.recovery is not None else None,
        )
        self.queue.add(shape, adm, now)
        return ticket

    def pump(self, now: Optional[float] = None, force: bool = False) -> int:
        """Dispatch every due megabatch; returns requests completed."""
        now = self.clock() if now is None else now
        done = 0
        for shape, items in self.queue.pop_due(now, force=force):
            done += self._dispatch(shape, items)
        return done

    def drain(self) -> int:
        """Flush everything pending regardless of age; returns requests
        completed."""
        return self.pump(force=True)

    def solve_many(self, problems: Sequence[OTProblem]) -> List[SinkhornResult]:
        """Convenience batch entry: submit all, drain, return results in
        submission order (the serving twin of ``BatchedSinkhorn.solve_many``)."""
        tickets = [self.submit(p) for p in problems]
        self.drain()
        return [t.result for t in tickets]

    def next_deadline(self) -> Optional[float]:
        return self.queue.next_deadline()

    def pending(self) -> int:
        return len(self.queue)

    # -- planning ------------------------------------------------------------

    def warmup(
        self,
        cells: Iterable[Union[OTBatchShape, Tuple[int, int, int]]],
        batches: Optional[Iterable[int]] = None,
    ) -> int:
        """Pre-plan runners for the expected traffic shapes.

        ``cells`` are :class:`OTBatchShape`\\ s or raw ``(n, m, r)``
        support triples (bucketed here); every batch bucket up to
        ``max_batch`` is compiled per cell unless ``batches`` narrows it.
        Returns the number of runners built.
        """
        shapes = []
        for c in cells:
            if isinstance(c, OTBatchShape):
                shapes.append(c)
            else:
                n, m, r = c
                shapes.append(
                    OTBatchShape.for_quadratic(n, m)
                    if self.engine.method in self.engine._QUADRATIC
                    else OTBatchShape.for_problem(n, m, r)
                )
        return self.runners.warm(shapes, batches)

    # -- dispatch ------------------------------------------------------------

    def _dispatch(self, shape: OTBatchShape, items: List[_Admitted]) -> int:
        b_real = len(items)
        b_pad = ot_batch_bucket(b_real, self.max_batch)
        # pad dead lanes by REPLICATING a real request: the duplicates
        # converge exactly like their source (no all-zero-weight lane to
        # NaN-poison or stall the batched while_loop) and are discarded
        lanes = items + [items[-1]] * (b_pad - b_real)
        quadratic = self.engine.method in self.engine._QUADRATIC
        kas, kbs, aws, bws, f0s, g0s = [], [], [], [], [], []
        for it in lanes:
            ka, kb = _pad_kernel_np(it.ka, it.kb, shape, quadratic)
            kas.append(ka)
            kbs.append(kb)
            aws.append(_pad_np(it.a, shape.n_pad, replicate=False))
            bws.append(_pad_np(it.b, shape.m_pad, replicate=False))
            if it.f0 is None:        # zeros == the cold default init
                f0s.append(np.zeros((shape.n_pad,), np.float32))
                g0s.append(np.zeros((shape.m_pad,), np.float32))
            else:
                f0s.append(_pad_np(it.f0, shape.n_pad, replicate=False))
                g0s.append(_pad_np(it.g0, shape.m_pad, replicate=False))
        # building the runner compiles it: errors there always propagate
        runner = self.runners.get(shape, b_pad)
        arrays = tuple(np.stack(x) for x in (kas, kbs, aws, bws, f0s, g0s))
        try:
            # per-request unpadding of ``host`` is pure numpy slicing
            host = _execute(runner, arrays, self.chaos_hook, (shape, b_pad))
        except _RunnerFault as exc:
            # runtime fault (chaos injection, a compiled runner raising):
            # with recovery enabled the megabatch is absorbed — every
            # request retries solo through the ladder, starting with a
            # cold re-run of the base config — otherwise the runner's own
            # exception propagates
            if self.recovery is None:
                raise exc.__cause__
            self.runner_faults += 1
            for it in items:
                self._recover_one(it, None, fault=exc.__cause__)
            self.served += b_real
            self.batches += 1
            return b_real
        t_done = self.clock()
        for j, it in enumerate(items):
            r = _unpad_np(host, j, it.n, it.m)
            h = classify(r, f_init=it.f0, g_init=it.g0, a=it.a, b=it.b)
            self.health_hist[h.verdict] += 1
            it.ticket.health = h
            if self.recovery is not None and \
                    h.verdict not in self.recovery.accept:
                self._recover_one(it, h)
                continue
            it.ticket.result = r
            it.ticket.t_done = t_done
            if self.warm_starts:
                self.warm.store(it.support_key, it.full_key, r.f, r.g,
                                it.a, it.b)
            iters = int(r.n_iter)
            if it.ticket.warm_hit:
                self.served_warm += 1
                self.iters_warm += iters
            else:
                self.served_cold += 1
                self.iters_cold += iters
        self.served += b_real
        self.batches += 1
        return b_real

    # -- recovery ladder -----------------------------------------------------

    def _base_state(self) -> Dict[str, object]:
        e = self.engine
        return dict(method=e.method, precision=e.precision,
                    use_pallas=e.use_pallas, inner_steps=e.inner_steps,
                    check_every=e.check_every)

    @staticmethod
    def _cfg_key(state: Dict[str, object], eps: float) -> Tuple:
        return (state["method"], float(eps), state["precision"],
                state["use_pallas"], state["inner_steps"],
                state["check_every"])

    def _rung_cache(self, state: Dict[str, object],
                    eps: float) -> RunnerCache:
        """Batch-1 RunnerCache for one cumulative ladder configuration.
        The engine is built DIRECTLY (not via ``get_engine``) so recovery
        traffic never churns the global engine LRU; runner compiles are
        still one-time per (config, cell) and pre-payable through
        :meth:`warmup_recovery`."""
        key = self._cfg_key(state, eps)
        cache = self._rung_caches.get(key)
        if cache is None:
            engine = BatchedSinkhorn(
                eps=float(eps), method=state["method"],
                tol=self.engine.tol, max_iter=self.engine.max_iter,
                momentum=self.engine.momentum,
                use_pallas=state["use_pallas"],
                inner_steps=state["inner_steps"],
                check_every=state["check_every"],
                precision=state["precision"],
            )
            cache = self._rung_caches[key] = RunnerCache(
                engine, capacity=8, max_batch=1)
        return cache

    def _apply_rung(self, state: Dict[str, object], rung: str,
                    it: _Admitted, first_cold: bool,
                    any_applied: bool) -> Tuple[bool, Optional[float]]:
        """Mutate ``state`` for one rung; returns ``(applicable,
        stage_eps)``. Inapplicable rungs (already in that state, geometry
        can't support it) return False and consume no attempt. Rungs are
        CUMULATIVE: each later rung keeps the degradations before it."""
        if rung == "log_domain":
            twin = LOG_TWIN.get(state["method"])
            if twin is None or state["method"] in LOG_METHODS:
                return False, None
            state["method"] = twin
            return True, None
        if rung == "precision_f32":
            if state["precision"] == "highest":
                return False, None
            state["precision"] = "highest"
            return True, None
        if rung == "raise_eps":
            geom = it.problem.geometry if it.problem is not None else None
            if geom is None or not getattr(geom, "anneal_capable", False):
                return False, None
            return True, float(self.engine.eps) * self.recovery.eps_scale
        if rung == "per_iteration":
            if (state["use_pallas"] is False and state["inner_steps"] == 1
                    and state["check_every"] == 1):
                return False, None
            state.update(use_pallas=False, inner_steps=1, check_every=1)
            return True, None
        if rung == "cold_restart":
            # every recovery attempt already solves cold, so a bare
            # restart only adds information when nothing cold has run
            # yet: a poisoned/warm first attempt, or a runner fault
            return (not any_applied and not first_cold), None
        return False, None

    def _run_rung(self, state: Dict[str, object], it: _Admitted,
                  eps: float, f0: Optional[np.ndarray],
                  g0: Optional[np.ndarray]) -> SinkhornResult:
        """One solo solve of ``it`` under a ladder configuration, through
        that configuration's pre-planned batch-1 runner."""
        cache = self._rung_cache(state, eps)
        engine = cache.engine
        # re-derive kernel data under the rung's method/eps (log features
        # for the log twin, geometry rebuilt for a raised eps)
        ka, kb = engine.kernel_data(it.problem)
        ka = np.asarray(ka, np.float32)
        kb = np.asarray(kb, np.float32)
        shape = engine.batch_shape(ka, kb)
        quadratic = engine.method in engine._QUADRATIC
        pka, pkb = _pad_kernel_np(ka, kb, shape, quadratic)
        pa = _pad_np(it.a, shape.n_pad, replicate=False)
        pb = _pad_np(it.b, shape.m_pad, replicate=False)
        if f0 is None:
            pf = np.zeros((shape.n_pad,), np.float32)
            pg = np.zeros((shape.m_pad,), np.float32)
        else:
            pf = _pad_np(np.asarray(f0, np.float32), shape.n_pad,
                         replicate=False)
            pg = _pad_np(np.asarray(g0, np.float32), shape.m_pad,
                         replicate=False)
        runner = cache.get(shape, 1)
        host = _execute(runner, tuple(x[None] for x in (pka, pkb, pa, pb,
                                                        pf, pg)))
        return _unpad_np(host, 0, it.n, it.m)

    def _attempt(self, state: Dict[str, object], it: _Admitted,
                 stage_eps: Optional[float]) -> SinkhornResult:
        if stage_eps is None:
            return self._run_rung(state, it, float(self.engine.eps),
                                  None, None)
        # raise_eps is TWO stages with warm handoff — the EpsSchedule
        # cascade semantics: solve cold at the raised (easy) eps, then
        # anneal back down to the service eps warm-started from the
        # stage-1 potentials. Non-finite stage-1 entries (legitimate
        # -inf on dead atoms) hand off as 0, the cold init for that atom.
        r1 = self._run_rung(state, it, stage_eps, None, None)
        f1 = np.asarray(r1.f)
        g1 = np.asarray(r1.g)
        f0 = np.where(np.isfinite(f1), f1, 0.0)
        g0 = np.where(np.isfinite(g1), g1, 0.0)
        return self._run_rung(state, it, float(self.engine.eps), f0, g0)

    def _recover_one(self, it: _Admitted, first_health: Optional[SolveHealth],
                     fault: Optional[Exception] = None) -> None:
        """Climb the recovery ladder for one failed request. Terminal:
        fills either ``ticket.result`` (+health) or ``ticket.refusal``."""
        pol = self.recovery
        ticket = it.ticket
        if first_health is not None:
            order = pol.ordered_rungs(first_health.verdict)
        else:
            # runner fault: nothing numerical happened — retry the base
            # config cold first, then the standard ladder
            order = ("cold_restart",) + tuple(
                r for r in pol.rungs if r != "cold_restart")
        deadline = (time.monotonic() + pol.deadline_s
                    if pol.deadline_s is not None else None)
        state = self._base_state()
        applied: List[str] = []
        attempts = 1                       # the failed batched attempt
        last_health = first_health
        stage: Optional[float] = None      # sticks once raise_eps applies
        for rung in order:
            if attempts >= pol.max_attempts:
                break
            if deadline is not None and time.monotonic() > deadline:
                break
            ok, stage_eps = self._apply_rung(
                state, rung, it, first_cold=(it.f0 is None and fault is None),
                any_applied=bool(applied))
            if not ok:
                continue
            if stage_eps is not None:
                # cumulative: later rungs keep the two-stage eps cascade
                stage = stage_eps
            applied.append(rung)
            attempts += 1
            self.recovery_attempts += 1
            try:
                r = self._attempt(state, it, stage)
            except _RunnerFault:
                self.runner_faults += 1
                continue
            h = classify(r, a=it.a, b=it.b)
            last_health = h
            if h.verdict in pol.accept:
                ticket.result = r
                ticket.health = h
                ticket.t_done = self.clock()
                ticket.attempts = attempts
                ticket.rungs = tuple(applied)
                if self.warm_starts:
                    self.warm.store(it.support_key, it.full_key, r.f, r.g,
                                    it.a, it.b)
                self.recovered += 1
                self.rung_hist[rung] += 1
                self.served_cold += 1
                self.iters_cold += int(r.n_iter)
                return
        # ladder exhausted: structured refusal, never a NaN result
        reason = "runner_fault" if (fault is not None and not applied) \
            else "recovery_exhausted"
        detail = (f"{type(fault).__name__}: {fault}" if fault is not None
                  else f"ladder exhausted after {attempts} attempts "
                       f"(rungs tried: {applied or ['none applicable']})")
        ticket.refusal = Refusal(reason=reason, detail=detail,
                                 health=last_health)
        ticket.health = last_health
        ticket.t_done = self.clock()
        ticket.attempts = attempts
        ticket.rungs = tuple(applied)
        self.refused += 1
        count = self._quarantine.get(it.full_key, 0) + 1
        self._quarantine[it.full_key] = count
        self._quarantine.move_to_end(it.full_key)
        while len(self._quarantine) > self.quarantine_capacity:
            self._quarantine.popitem(last=False)

    def warmup_recovery(
        self,
        cells: Iterable[Union[OTBatchShape, Tuple[int, int, int]]],
        *,
        anneal: bool = True,
    ) -> int:
        """Pre-plan the batch-1 rung runners every ladder prefix can reach
        for the expected traffic cells — the recovery twin of
        :meth:`warmup`, and what keeps retries free of retrace storms
        (the chaos CI gate counts post-warmup compiles across rung caches
        too). ``anneal=False`` skips the raised-eps configs when no
        traffic geometry is anneal-capable. Returns runners built."""
        if self.recovery is None:
            return 0
        shapes = []
        for c in cells:
            if isinstance(c, OTBatchShape):
                shapes.append(c)
            else:
                n, m, r = c
                shapes.append(
                    OTBatchShape.for_quadratic(n, m)
                    if self.engine.method in self.engine._QUADRATIC
                    else OTBatchShape.for_problem(n, m, r)
                )
        base_eps = float(self.engine.eps)
        raised_eps = base_eps * self.recovery.eps_scale
        # walk the cumulative ladder, collecting every state a recovery
        # could solve under (cold_restart = the base state)
        states = [self._base_state()]
        state = self._base_state()
        for rung in self.recovery.rungs:
            if rung == "log_domain":
                twin = LOG_TWIN.get(state["method"])
                if twin is None or state["method"] in LOG_METHODS:
                    continue
                state["method"] = twin
            elif rung == "precision_f32":
                if state["precision"] == "highest":
                    continue
                state["precision"] = "highest"
            elif rung == "per_iteration":
                state.update(use_pallas=False, inner_steps=1, check_every=1)
            else:           # raise_eps / cold_restart don't mutate state
                continue
            states.append(dict(state))
        # the raised-eps stage composes with EVERY cumulative state (a
        # later rung keeps the eps cascade), so warm each state at both
        # eps levels
        configs = [(st, base_eps) for st in states]
        if anneal and "raise_eps" in self.recovery.rungs:
            configs += [(st, raised_eps) for st in states]
        built = 0
        for st, eps in configs:
            built += self._rung_cache(st, eps).warm(shapes, batches=(1,))
        return built

    # -- accounting ----------------------------------------------------------

    def stats(self) -> Dict[str, object]:
        """All serving-path cache/throughput counters in one snapshot:
        runner cache (compiles = misses, steady-state hits, retraces),
        warm-start cache (exact/near hit rates), the GLOBAL engine LRU
        (this service's engine is one entry in it), and per-class mean
        iteration counts (the measured warm-start win)."""
        return dict(
            runner=self.runners.snapshot(),
            warm=self.warm.snapshot(),
            engine=engine_cache_info(),
            served=self.served,
            batches=self.batches,
            pending=self.pending(),
            mean_batch=self.served / self.batches if self.batches else 0.0,
            mean_iters_warm=(self.iters_warm / self.served_warm
                             if self.served_warm else 0.0),
            mean_iters_cold=(self.iters_cold / self.served_cold
                             if self.served_cold else 0.0),
            shed=self.queue.shed,
            health=dict(self.health_hist),
            recovery=dict(
                enabled=self.recovery is not None,
                attempts=self.recovery_attempts,
                recovered=self.recovered,
                refused=self.refused,
                runner_faults=self.runner_faults,
                quarantine_rejects=self.quarantine_rejects,
                quarantined=sum(
                    1 for c in self._quarantine.values()
                    if c >= self.quarantine_after),
                rung_hist=dict(self.rung_hist),
                rung_configs=len(self._rung_caches),
                rung_runners=sum(
                    len(c) for c in self._rung_caches.values()),
                rung_compiles=sum(
                    c.misses for c in self._rung_caches.values()),
                rung_extra_traces=sum(
                    c.extra_traces for c in self._rung_caches.values()),
            ),
        )
