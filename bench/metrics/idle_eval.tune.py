"""Share of the traced window in which the device sat idle inside the
executor's evaluation and decisions: ``tune.eval`` (building the eval
batches), ``tune.eval_fetch`` (each chunk's launch and its losses to the
host) and ``tune.decide`` (monitors, exits, selection, backfill), the
innermost program span at each idle instant; in percent."""
from bench import spans


def read(run, ctx):
    return spans.idle_share(spans.of_run(run, ctx), spans.TUNE_EVAL)
