#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (a configuration under a traffic mix) is looked up by name in
``BENCHMARK.json``; everything else is found by the names in it (see
``bench/harness.py``). The last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each number the
correctness comparison compared, beside its limit). The same numbers are
the last lines of standard error. With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

A run that finds no TPU, or fewer chips than the cell asks for, exits 2
and prints no result.

``--rehearse`` runs the cell end to end on whatever device JAX finds, at
the tiny sizes in ``harness.REHEARSAL`` and the mix's ``rehearse`` values
(the CPU rehearsal).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the checkout, not this directory, goes first on the path: bench/trace.py
# must not stand in for the standard library's trace module
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    from bench import harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    try:
        bench = harness.load_json(ROOT, "BENCHMARK.json")
        ctx = harness.Context(bench, args.workload, args.seed, args.seconds,
                              bool(args.trace), args.rehearse)
        harness.use_compile_cache(args.rehearse)
        device = harness.check_device(ctx)
        driver = harness.load_module(
            os.path.join(harness.BENCH, "drivers",
                         ctx.traffic["driver"] + ".py"),
            "bench_driver_" + ctx.traffic["driver"])
        run = driver.run(ctx, T_START)
        out = harness.result_line(ctx, run, device, run.e2e["setup_s"])
        out_counters = dict(run.counters)
    except (harness.BenchError, OSError, KeyError, ImportError) as e:
        print(f"bench: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps({"counters": out_counters}), file=sys.stderr)
    harness.print_checks(run)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
