"""The program's spans and counters in the benchmark: ``spans.program_summary``
on events written by hand and on a small trace recorded on a TPU v5e
(``fixtures/tiny_tpu_ot.xplane.pb``), and the readers of ``front_door_ms``,
``loop_launch_ms`` and ``loop_traces_per_call``."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness, tracefile
from bench.spans import program_summary
from bench.tracefile import Event

REPO = Path(__file__).resolve().parents[2]
FIXTURE = Path(__file__).resolve().parent / "fixtures" / "tiny_tpu_ot.xplane.pb"
READERS = ("front_door_ms", "loop_launch_ms", "loop_traces_per_call")


def hand_trace():
    ops = {"/device:TPU:0": [Event("fusion.1", 150, 200),
                             Event("loop.2", 260, 300),
                             Event("fusion.3", 420, 430)]}
    host = [Event("bench.call", 100, 110),
            Event("ot.solve", 50, 310),        # starts before the window
            Event("ot.featurize", 120, 140),
            Event("ot.loop", 210, 300),
            Event("ot.solve", 220, 240),       # nested in its own name
            Event("bench.block", 310, 400),
            Event("ot.finish", 380, 450),      # ends after the window
            Event("host_work", 320, 360)]
    return ops, host


def test_program_summary_clips_to_the_window_and_counts_nesting_once():
    s = program_summary(*hand_trace())
    assert s.window_s == pytest.approx(300e-9)
    assert s.spans["ot.solve"] == (pytest.approx(210e-9), 1)
    assert s.spans["ot.loop"] == (pytest.approx(90e-9), 1)
    assert s.spans["ot.finish"] == (pytest.approx(20e-9), 1)


def test_program_idle_counts_only_idle_inside_program_spans():
    s = program_summary(*hand_trace())
    # program spans cover [100, 310] and [380, 400]; the device is busy
    # [150, 200] and [260, 300]: idle inside is 50 + 60 + 10 + 20, while
    # [310, 380] (bench.block, host_work) stays outside
    assert s.idle_s == pytest.approx(140e-9)
    assert s.idle_share == pytest.approx(140 / 300)
    # each gap goes to the innermost ot.* span at its midpoint: [100, 150]
    # to ot.featurize, [200, 260] to the nested ot.solve, [300, 310] to
    # the outer one, [380, 400] to ot.finish
    assert dict(s.idle_by_span) == {"ot.featurize": pytest.approx(50e-9),
                                    "ot.solve": pytest.approx(70e-9),
                                    "ot.finish": pytest.approx(20e-9)}
    whole = tracefile.summarize(*hand_trace())
    assert s.idle_s <= whole.window_s - whole.busy_s


def test_no_device_ops_raises():
    with pytest.raises(ValueError):
        program_summary({}, [])


def test_recorded_tpu_trace_shares_the_device_clock():
    ops, host = tracefile.read_xplane(str(FIXTURE))
    assert ops and all(k.startswith("/device:TPU:") for k in ops)
    calls = [e for e in host if e.name == tracefile.CALL_SPAN]
    loops = sorted((e for e in host if e.name == "ot.loop"),
                   key=lambda e: e.start_ns)
    assert len(loops) == len(calls) >= 2
    whiles = sorted((e for evs in ops.values() for e in evs
                     if e.name == "while"), key=lambda e: e.start_ns)
    assert len(whiles) == len(loops)
    # each call's while program starts inside its own ot.loop span, and
    # only after most of it: the host traces, lowers and compiles the loop
    # while the device waits
    for w, loop in zip(whiles, loops):
        assert loop.start_ns < w.start_ns < loop.end_ns
        assert w.start_ns - loop.start_ns > 0.5 * (loop.end_ns -
                                                   loop.start_ns)
    s = program_summary(ops, host)
    whole = tracefile.summarize(ops, host)
    assert {"ot.solve", "ot.featurize", "ot.loop", "ot.finish"} <= \
        set(s.spans)
    assert s.spans["ot.solve"][1] == len(calls)
    assert 0 < s.idle_s <= whole.window_s - whole.busy_s + 1e-9


@pytest.fixture
def traced(monkeypatch):
    """Stand in for what a profiled window leaves in ``repro.obs``."""
    import repro.obs as obs

    def set_to(spans, counters):
        monkeypatch.setattr(obs, "traced", lambda: dict(spans=spans,
                                                        counters=counters))
    return set_to


def _run(calls=4):
    rec = harness.CallRecord(failed=False, iters=200,
                             problems=[(8, 8, 4, 2, 200)])
    return harness.Run(device_kind="TPU v5 lite", chips=1, setup_s=1.0,
                       window_s=8.0, calls=[rec] * calls, window_compiles=4,
                       peak_bytes=0)


def _read(name, run):
    return harness.load_module(harness.BENCH, "metrics", name).read(run)


def test_readers_read_the_traced_tallies(traced):
    traced({"ot.solve": (0.2, 4), "ot.featurize": (0.04, 4),
            "ot.loop": (0.24, 4), "ot.finish": (0.02, 4),
            "ot.other": (0.1, 1)},          # any ot.* self time counts
           {"ot.loop.traces": 4, "ot.solve.calls": 4})
    run = _run()
    assert _read("front_door_ms", run) == pytest.approx(150.0)
    assert _read("loop_launch_ms", run) == pytest.approx(60.0)
    assert _read("loop_traces_per_call", run) == 1.0


def test_cached_loop_reads_zero(traced):
    traced({"ot.solve_many": (0.04, 4), "ot.stage": (0.08, 8)}, {})
    run = _run()
    assert _read("front_door_ms", run) == pytest.approx(30.0)
    assert _read("loop_launch_ms", run) == 0.0
    assert _read("loop_traces_per_call", run) == 0.0


def test_readers_read_none_without_spans_or_without_the_module(
        traced, monkeypatch):
    traced({}, {"ot.loop.traces": 3})
    for name in READERS:
        assert _read(name, _run()) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    for name in READERS:
        assert _read(name, _run()) is None


TRACED_RUN = r'''
import json, sys, tempfile
from pathlib import Path
from bench import tracefile
from bench.tests.tiny import run_cell, tiny_root
# the CPU trace has no device plane: stand in a summary, keep the window
tracefile.summarize = lambda ops, host: tracefile.TraceSummary(
    window_s=1.0, devices=1, busy_s=0.5, collective_exposed_s=None,
    device_ops=[], idle_gaps=[])
root = tiny_root(Path(tempfile.mkdtemp()))
spec = json.loads((root.parent / "BENCHMARK.json").read_text())
spec["per_layer"] = [m for m in spec["per_layer"]
                     if m["name"] in sys.argv[1:]]
(root.parent / "BENCHMARK.json").write_text(json.dumps(spec))
rc, last, err = run_cell(root, "higgs_tiny.solve", trace=1, seconds=1.0)
print(json.dumps(dict(rc=rc, last=last, err=err[-2000:])))
'''


def test_traced_run_reports_the_new_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(REPO), str(REPO / "src")]))
    proc = subprocess.run([sys.executable, "-c", TRACED_RUN, *READERS],
                          cwd=REPO, env=env, capture_output=True, text=True,
                          timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got["rc"] == 0, got["err"]
    metrics = {k: v["value"] for k, v in got["last"]["metrics"].items()}
    assert set(metrics) == set(READERS)
    # every eager solve(spec) re-traces its loop
    assert metrics["loop_traces_per_call"] == 1.0
    assert 0 < metrics["loop_launch_ms"] < metrics["front_door_ms"]
