"""Spans and counters at the front door and the solver loop (``repro.obs``):
what a profiler session records, the loop's re-trace counter, and that
nothing is recorded, and nothing changes, with no profiler running."""
import glob

import jax
import numpy as np
import pytest

from repro import obs
from repro.core import solve, solve_many
from repro.core.geometry import GaussianPointCloud
from repro.core.spec import SolveSpec
from repro.kernels import observe_plan_selection as from_kernels
from repro.kernels.ops import observe_plan_selection as from_ops

EPS = 0.5


def _spec(seed=0, n=48, r=16, d=2):
    kx, ky, ka = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (n, d))
    y = 0.7 * jax.random.normal(ky, (n, d))
    anchors = jax.random.normal(ka, (r, d))
    geom = GaussianPointCloud.build(x, y, anchors, eps=EPS, R=4.0)
    return SolveSpec(geometry=geom, method="log_factored", tol=1e-4,
                     max_iter=500)


def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler session; returns (its result, the host
    spans of the trace as (name, start, end, stats))."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        out = jax.block_until_ready(fn())
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("ot.")]
    return out, spans


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_solve_records_nested_spans_with_a_call_id(tmp_path):
    spec = _spec()
    before = obs.counters().get("ot.solve.calls", 0)
    _, spans = _profiled(tmp_path, lambda: solve(spec))
    top = [s for s in spans if s[0] == "ot.solve"]
    assert len(top) == 1
    assert top[0][3] == {"call": before + 1}
    for name in ("ot.featurize", "ot.loop", "ot.finish"):
        mine = [s for s in spans if s[0] == name]
        assert mine and all(_inside(s, top[0]) for s in mine), name
    loop, = [s for s in spans if s[0] == "ot.loop"]
    fin, = [s for s in spans if s[0] == "ot.finish"]
    assert loop[2] <= fin[1]


def test_solve_many_records_staging_inside_the_call(tmp_path):
    specs = [_spec(seed) for seed in range(3)]
    _, spans = _profiled(tmp_path, lambda: solve_many(specs))
    top = [s for s in spans if s[0] == "ot.solve_many"]
    stages = [s for s in spans if s[0] == "ot.stage"]
    assert len(top) == 1 and stages
    assert all(_inside(s, top[0]) for s in stages)


def test_traced_tallies_add_up_to_the_outermost_span(tmp_path):
    spec = _spec()
    before = obs.traced()
    _, spans = _profiled(tmp_path, lambda: solve(spec))
    after = obs.traced()

    def delta(name):
        s0, c0 = before["spans"].get(name, (0.0, 0))
        s1, c1 = after["spans"].get(name, (0.0, 0))
        return s1 - s0, c1 - c0

    assert delta("ot.solve")[1] == 1 and delta("ot.loop")[1] == 1
    total = sum(delta(n)[0] for n in ("ot.solve", "ot.featurize", "ot.loop",
                                      "ot.finish"))
    top, = [s for s in spans if s[0] == "ot.solve"]
    assert total == pytest.approx((top[2] - top[1]) * 1e-9, rel=0.05,
                                  abs=2e-4)
    assert all(delta(n)[0] > 0 for n in ("ot.solve", "ot.loop"))
    assert after["counters"].get("ot.loop.traces", 0) - \
        before["counters"].get("ot.loop.traces", 0) == 1


def test_loop_traces_once_per_eager_solve_and_not_on_a_cached_jit():
    spec = _spec()

    def traces():
        return obs.counters().get("ot.loop.traces", 0)

    t0 = traces()
    solve(spec)
    solve(spec)
    assert traces() - t0 == 2

    x, y = spec.geometry.x, spec.geometry.y
    anchors = spec.geometry.anchors

    @jax.jit
    def cost(x, y):
        geom = GaussianPointCloud.build(x, y, anchors, eps=EPS, R=4.0)
        return solve(SolveSpec(geometry=geom, method="log_factored",
                               tol=1e-4, max_iter=500)).cost

    t1 = traces()
    first = cost(x, y)
    assert traces() - t1 == 1
    second = cost(x + 0.0, y)
    assert traces() - t1 == 1
    assert float(first) == float(second)


def test_no_profiler_records_nothing_and_changes_nothing(tmp_path):
    spec = _spec()
    before = obs.traced()
    plain = solve(spec)
    assert obs.traced() == before
    traced, _ = _profiled(tmp_path, lambda: solve(spec))
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_counters_are_copies_and_count_returns_the_total():
    n = obs.count("test.obs.counter", 3)
    assert obs.count("test.obs.counter") == n + 1
    snap = obs.counters()
    snap["test.obs.counter"] = -1
    assert obs.counters()["test.obs.counter"] == n + 1


def test_plan_hook_is_one_observer_list_reexported():
    assert from_ops is from_kernels is obs.observe_plan_selection
    with obs.observe_plan_selection() as events:
        obs.notify_plan_selected({"mode": "log"})
    obs.notify_plan_selected({"mode": "unseen"})
    assert events == [{"mode": "log"}]


def test_span_costs_little_without_a_profiler():
    import time
    n = 20000
    t = time.perf_counter()
    for i in range(n):
        with obs.span("ot.test", call=i):
            pass
    assert (time.perf_counter() - t) / n < 50e-6


@pytest.mark.parametrize("method, fallbacks", [("sharded_log", 0),
                                               ("sharded", 1)])
def test_sharded_solve_records_its_span_and_fallbacks(tmp_path, method,
                                                      fallbacks):
    """``ot.shard`` covers the host part of a sharded solve, inside
    ``ot.solve``; a log solve asked for the fused kernels runs the
    per-shard plan, a scaling one has none and counts a fallback."""
    from jax.sharding import Mesh
    from repro.core.objective import ExecutionPolicy

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    spec = _spec().replace(method=method, policy=ExecutionPolicy(
        mesh=mesh, use_pallas=True))
    before = obs.traced()
    with obs.observe_plan_selection() as events:
        _, spans = _profiled(tmp_path, lambda: solve(spec))
    after = obs.traced()
    top, = [s for s in spans if s[0] == "ot.solve"]
    shard, = [s for s in spans if s[0] == "ot.shard"]
    assert _inside(shard, top)
    s0, c0 = before["spans"].get("ot.shard", (0.0, 0))
    s1, c1 = after["spans"].get("ot.shard", (0.0, 0))
    assert c1 - c0 == 1 and s1 > s0
    assert after["counters"].get("ot.shard.fallbacks", 0) - \
        before["counters"].get("ot.shard.fallbacks", 0) == fallbacks
    assert [e["axis"] for e in events] == ["data"] * (1 - fallbacks)
