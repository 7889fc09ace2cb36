#!/usr/bin/env python3
"""Readings that the correctness limits are set from, in one process:
the program's on many seeds, the control's (the reference in the precision
below the configuration's, in the program's place) and a planted fault's.

    python3 bench/calibrate.py --workload <name> --seeds 11,12,13 \\
        [--control] [--fault half_batch] [--seconds 0] [--chunks 3] \\
        [--rates 1,2] [--out file.jsonl]

Each seed is one run of the cell's driver, its window ``--seconds`` long
(a serving window has to finish the mix's longest requests; a tuning
window is the mix's fixed number of chunks). One JSON line per seed: the
compared numbers and ``correct``; with ``--control`` the control's numbers
and its ``correct`` by the same limits, which has to be false; and the
run's counters. ``--rates`` gives a serving run's arrival rate, one per
seed (the sweep that finds the knee). The benchmark's own runs never run
the control or a fault.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[0] = ROOT
sys.path.insert(1, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    import argparse
    from bench import faults, harness
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--control", action="store_true",
                    help="also run the control and hold it to the limits")
    ap.add_argument("--fault", choices=sorted(faults.FAULTS), default=None)
    ap.add_argument("--chunks", type=int, default=None,
                    help="tuning: end the window after this many chunks "
                         "(its readings need only the chunk in which the "
                         "admitted job's recorded steps end)")
    ap.add_argument("--rates", default=None,
                    help="serving: one arrival rate per seed (the knee "
                         "sweep)")
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = harness.load_json(ROOT, "BENCHMARK.json")
    harness.use_compile_cache(args.rehearse)
    seeds = [int(s) for s in args.seeds.split(",")]
    rates = ([float(r) for r in args.rates.split(",")] if args.rates
             else [None] * len(seeds))
    undo = faults.FAULTS[args.fault]() if args.fault else None
    try:
        for seed, rate in zip(seeds, rates):
            ctx = harness.Context(bench, args.workload, seed, args.seconds,
                                  False, args.rehearse)
            if args.control:
                ctx.use_control()
            ctx.detail = True
            if rate is not None:
                ctx.traffic["rate_per_s"] = rate
            if args.chunks is not None:
                ctx.traffic["window_chunks"] = args.chunks
            harness.check_device(ctx)
            driver = harness.load_module(
                os.path.join(harness.BENCH, "drivers",
                             ctx.traffic["driver"] + ".py"),
                "bench_driver_" + ctx.traffic["driver"])
            run = driver.run(ctx, time.perf_counter())
            line = {"workload": args.workload, "seed": seed,
                    "fault": args.fault, "rate": rate, "e2e": run.e2e,
                    "correct": run.correct,
                    "checks": {c["name"]: c["value"] for c in run.checks},
                    "control_correct": run.control_correct,
                    "control": {c["name"]: c["value"]
                                for c in run.control_checks} or None,
                    "counters": run.counters}
            print(json.dumps(line), flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps(line) + "\n")
            del run, driver, ctx
            harness.free()
    finally:
        if undo:
            undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
