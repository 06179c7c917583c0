"""A new configuration, cell and metric are found by name: files dropped into
a benchmark root and entries in its BENCHMARK.json, no edit to any other
file."""
import json

import pytest

from bench import harness
from bench.tests.tiny import CONFIGS, CELLS, run_cell, tiny_root


@pytest.fixture
def root(tmp_path):
    configs = dict(CONFIGS, higgs_other=dict(CONFIGS["higgs_tiny"], r=64,
                                             n=512))
    cells = dict(CELLS, **{"higgs_other.solve": dict(
        config="higgs_other", traffic="solve", driver="solve",
        params={"pool_seed": 0, "instances": 1})})
    root = tiny_root(tmp_path, configs, cells)
    (root / "metrics" / "calls_made.py").write_text(
        "def read(run):\n    return float(len(run.calls))\n")
    (root / "metrics" / "lanes_solved.py").write_text(
        "def read(run):\n"
        "    return float(sum(len(c.problems) for c in run.calls))\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["end_to_end"].append(dict(name="calls_made", unit="count",
                                   better="higher", bound=0.1,
                                   source="host_clock",
                                   workloads=["higgs_other.solve"]))
    spec["per_layer"].append(dict(name="lanes_solved", unit="count",
                                  better="higher", source="program_counter",
                                  layer="front door", moves="solve_s"))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_new_cell_config_and_metric_are_found(root):
    cell = harness.load_cell("higgs_other.solve", root)
    assert cell.config["r"] == 64 and cell.chips == 1
    assert "calls_made" in [m["name"] for m in cell.end_to_end]
    assert "lanes_solved" in [m["name"] for m in cell.per_layer]
    other = harness.load_cell("higgs_tiny.solve", root)
    assert "calls_made" not in [m["name"] for m in other.end_to_end]


def test_new_cell_runs_and_reports_new_metric(root):
    rc, last, err = run_cell(root, "higgs_other.solve")
    assert rc == 0, err
    assert last["correct"] is True
    assert last["metrics"]["calls_made"]["value"] == last["attempted"]
    assert set(last["metrics"]) == {"solve_s", "peak_hbm_gib", "setup_s",
                                    "calls_made"}
    assert list(last)[-1] == "checks"
    assert set(last["checks"]) == {"row_err", "col_err"}


def test_new_per_layer_reader_reads_a_run(root):
    rec = harness.CallRecord(failed=False, iters=5,
                             problems=[(8, 8, 4, 2, 5), (8, 8, 4, 2, 3)])
    run = harness.Run(device_kind="TPU v5 lite", chips=1,
                      setup_s=1.0, window_s=2.0, calls=[rec, rec],
                      window_compiles=0, peak_bytes=0)
    mod = harness.load_module(root, "metrics", "lanes_solved")
    assert mod.read(run) == 4.0


def test_cell_must_match_benchmark_entry(root, tmp_path):
    path = root / "workloads" / "higgs_other.solve.json"
    cell = json.loads(path.read_text())
    path.write_text(json.dumps(dict(cell, config="higgs_tiny")))
    with pytest.raises(ValueError):
        harness.load_cell("higgs_other.solve", root)


def test_unknown_cell_and_missing_file_raise(root):
    with pytest.raises(KeyError):
        harness.load_cell("nope.solve", root)
    with pytest.raises(FileNotFoundError):
        harness.load_module(root, "metrics", "nope")
