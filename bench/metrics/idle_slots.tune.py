"""Share of the traced window in which the device sat idle inside the
executor's slot copies and writes: ``tune.best_ckpt`` (a best-loss
adapter to the host), ``tune.snapshot`` (a rotated-out job's adapter and
moments to the host), ``tune.admit``, ``tune.restore`` and ``tune.evict``,
the innermost program span at each idle instant; in percent."""
from bench import spans


def read(run, ctx):
    return spans.idle_share(spans.of_run(run, ctx), spans.TUNE_SLOTS)
