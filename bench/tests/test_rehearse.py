"""Every cell end to end on the CPU at the rehearsal's size: the result
line has the contract's keys, the cell's end-to-end metrics with
``--trace 0``, and each compared number beside its limit, last; the run
is correct by the rehearsal's limits."""
import pytest

from bench.tests._runs import CELLS, rehearse


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_end_to_end(workload):
    rc, result = rehearse(workload)
    assert rc == 0 and result is not None
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks" and result["checks"]
    assert result["correct"] is True, result["checks"]
    assert "setup_s" in result["metrics"]
    assert len(result["metrics"]) >= 2
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["device"]["count"] >= 1
