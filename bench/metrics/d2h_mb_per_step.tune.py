"""Megabytes (1e6 bytes) the executor copied from the device to the host
in the traced window, by the ``d2h_bytes`` counts of its spans (losses,
eval losses, best-loss adapters, slot snapshots), per train step (one
``tune.assemble`` span a step)."""
from bench import spans


def read(run, ctx):
    got = spans.of_run(run, ctx)
    if got is None or not spans.total(got, "tune.assemble", "spans"):
        return None
    moved = sum(s.get("d2h_bytes", 0) for s in got["sums"].values())
    return moved / 1e6 / spans.total(got, "tune.assemble", "spans")
