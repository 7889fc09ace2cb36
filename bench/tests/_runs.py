"""Drive ``bench/run.py`` in this process at the rehearsal's size."""
import contextlib
import io
import json

CELLS = ["stablelm-3b.tune.mixed-rank", "stablelm-3b.serve.chat-poisson",
         "granite-8b-l12.tune.mixed-width"]
SECONDS = {"tune": 1, "serve": 4}


def rehearse(workload: str, seed: int = 12345, trace: int = 0):
    """(exit code, last stdout line as a dict or None)."""
    from bench import run as bench_run
    out = io.StringIO()
    kind = workload.split(".")[1]
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(["--workload", workload, "--seed", str(seed),
                             "--seconds", str(SECONDS[kind]),
                             "--trace", str(trace), "--rehearse"])
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None)
