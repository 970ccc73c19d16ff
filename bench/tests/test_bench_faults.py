"""The harness, past its look for a chip, with the timed path broken
underneath: ``correct`` has to come out false for every fault a cell can
have, and true without one."""
import pytest

from bench.tests.faults import run_tiny

CASES = [("box-4M.knn-bulk", None), ("box-4M.knn-bulk", "half_batch"),
         ("box-4M.knn-bulk", "altered_answer"),
         ("box-4M.within-open", None), ("box-4M.within-open", "half_batch"),
         ("box-4M.within-open", "altered_answer"),
         ("box-4M.within-open", "stale_version")]


@pytest.mark.usefixtures("shared_engine")
@pytest.mark.parametrize("cell,fault", CASES)
def test_fault_is_caught(cell, fault):
    out = run_tiny(cell, fault)
    assert out.result["correct"] is (fault is None), out.check.values
    assert out.check.rows_checked == sum(r.rows for r in out.window.sent
                                         if r.answered)
    if fault == "stale_version":
        assert out.window.moved and out.check.values["dist_gap"] > 1e-3
