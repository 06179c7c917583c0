"""The harness drives a run with the timed path broken underneath, past its
look for a chip, and ``correct`` comes out false for each fault the cell
can have; the same cells run sound come out true."""
import pytest

from bench.tests.faults import FAULTS
from bench.tests.tiny import CELLS, run_cell, tiny_root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("faults"))


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(root, cell):
    rc, last, err = run_cell(root, cell)
    assert rc == 0, err
    assert last["correct"] is True, last["checks"]
    assert last["failed"] == 0 and last["attempted"] >= 1


@pytest.mark.parametrize("fault", ["unchanged_state", "answer_altered",
                                   "half_batch"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_fault_is_caught(root, cell, fault):
    rc, last, err = run_cell(root, cell, call=FAULTS[fault])
    assert rc == 0, err
    assert last["correct"] is False, (fault, last["checks"])
    assert any(c["value"] > c["limit"] for c in last["checks"].values())
