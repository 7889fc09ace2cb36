"""Share of the traced window in which the device sat idle while the
executor fed the train step: inside ``tune.assemble`` (batch assembly and
its copy to the device), ``tune.loss_fetch`` (the per-slot losses to the
host), ``tune.observe`` (the monitors' update) or ``tune.report`` (the
chunk report), the innermost program span at each idle instant; in
percent."""
from bench import spans


def read(run, ctx):
    return spans.idle_share(spans.of_run(run, ctx), spans.TUNE_FEED)
