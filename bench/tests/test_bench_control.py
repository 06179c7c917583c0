"""The control, the plain reference solver in the program's place with its
features' cross term at bf16_3x (the precision next below the
configuration's), comes out not correct in both kinds of single-chip cell,
at a size a CPU holds."""
import pytest

from bench import control
from bench.tests.tiny import CELLS, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("control"))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_is_not_correct(root, cell):
    got = list(control.readings(cell, "control", [11, 12], 0.3,
                                bench_root=root, require_accelerator=False))
    assert [g["correct"] for g in got] == [False, False], got
    for g in got:
        assert g["checks"]["row_err"]["value"] > \
            3 * g["checks"]["row_err"]["limit"] / 2


def test_program_readings_sit_under_the_limits(root):
    got = list(control.readings("higgs_tiny.solve", "program", [11], 0.3,
                                bench_root=root, require_accelerator=False))
    assert got[0]["correct"] is True, got
