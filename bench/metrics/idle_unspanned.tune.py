"""Share of the traced window in which the device sat idle and no program
span was open (the benchmark's own spans do not count): host work the
program's spans do not name; in percent. With ``idle_feed.tune``,
``idle_eval.tune`` and ``idle_slots.tune`` it sums to
``idle_share.tune``."""
from bench import spans


def read(run, ctx):
    return spans.idle_share(spans.of_run(run, ctx), [spans.UNSPANNED])
