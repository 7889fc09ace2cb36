"""A run with the timed path broken underneath comes out not correct: the
harness's look for a chip is skipped (the rehearsal), everything else of
the run is driven as on the chip, with each fault the cell can have
planted in the program (``bench/faults.py``). One chip has no exchange
between chips to leave out. The limits are the rehearsal's (the
``rehearse`` entries of ``bench/limits/<cell>.json``)."""
import time

import pytest

from bench import faults, harness
from bench.tests._runs import CELLS, SECONDS, rehearse

TUNE = ["stablelm-3b.tune.mixed-rank", "granite-8b-l12.tune.mixed-width"]
TUNE_FAULTS = ["unchanged_state", "half_batch", "stale_moments",
               "stale_adapter", "stale_rank"]
CASES = [(w, f) for w in TUNE for f in TUNE_FAULTS] + [
    ("stablelm-3b.serve.chat-poisson", "altered_token")]


@pytest.mark.parametrize("workload,fault", CASES)
def test_fault_is_not_correct(workload, fault):
    undo = faults.FAULTS[fault]()
    try:
        rc, result = rehearse(workload)
    finally:
        undo()
    assert rc == 0 and result is not None
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_control_is_not_correct(workload):
    """The control (the reference in fp8, the precision below the
    configuration's bf16) put in the program's place and held to the
    cell's limits comes out not correct, where the program in the same
    run is correct."""
    bench = harness.load_json(harness.ROOT, "BENCHMARK.json")
    ctx = harness.Context(bench, workload, 12345,
                          SECONDS[workload.split(".")[1]], False,
                          rehearse=True)
    ctx.use_control()
    driver = harness.load_module(
        f"{harness.BENCH}/drivers/{ctx.traffic['driver']}.py",
        "bench_driver_" + ctx.traffic["driver"])
    run = driver.run(ctx, time.perf_counter())
    assert run.correct is True, run.checks
    assert run.control_correct is False, run.control_checks
