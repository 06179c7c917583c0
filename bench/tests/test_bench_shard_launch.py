"""The four-chip cell's entries and its ``shard_launch_ms`` reader: the
cell asks for 4 chips and reports the two metrics of the sharded path,
the one-chip cell reports neither, and the reader reads the program's
``ot.shard`` span, or nothing where the program has none."""
import sys

import pytest

from bench import harness

NEW = ("collective_share", "shard_launch_ms")


@pytest.fixture
def traced(monkeypatch):
    """Stand in for what a profiled window leaves in ``repro.obs``."""
    import repro.obs as obs

    def set_to(spans):
        monkeypatch.setattr(obs, "traced",
                            lambda: dict(spans=spans, counters={}))
    return set_to


def _run(calls=4):
    rec = harness.CallRecord(failed=False, iters=200,
                             problems=[(8, 8, 4, 2, 200)])
    return harness.Run(device_kind="TPU v5 lite", chips=4, setup_s=1.0,
                       window_s=8.0, calls=[rec] * calls, window_compiles=0,
                       peak_bytes=0)


def _read(run):
    return harness.load_module(harness.BENCH, "metrics",
                               "shard_launch_ms").read(run)


def test_reads_the_shard_span_self_time_per_call(traced):
    traced({"ot.solve": (0.05, 4), "ot.shard": (0.12, 4),
            "ot.loop": (0.3, 4)})
    assert _read(_run()) == pytest.approx(30.0)


def test_reads_none_without_the_span_or_the_program(traced, monkeypatch):
    traced({"ot.solve": (0.05, 4), "ot.loop": (0.3, 4)})
    assert _read(_run()) is None
    monkeypatch.setitem(sys.modules, "repro.obs", None)
    assert _read(_run()) is None


def test_four_chip_cell_reports_the_sharded_metrics():
    cell = harness.load_cell("higgs_r256_x4.sharded_solve")
    assert cell.chips == 4
    assert cell.config["n"] == 2 ** 23 and cell.config["chips"] == 4
    assert cell.config["method"] == "sharded_log"
    assert cell.workload["driver"] == "sharded_solve"
    per_layer = [m["name"] for m in cell.per_layer]
    assert set(NEW) <= set(per_layer)
    one_chip = harness.load_cell("higgs_r256.solve")
    assert not set(NEW) & {m["name"] for m in one_chip.per_layer}
