"""Padding token positions over all token positions of the fused batches
assembled in the traced window, in percent: the ``positions``
(Z x b_cap x S_cap) and ``real_tokens`` counts of ``tune.assemble``,
taken where the executor builds each batch. The program-side twin of
``pad_share.tune``."""
from bench import spans


def read(run, ctx):
    got = spans.of_run(run, ctx)
    if got is None:
        return None
    positions = spans.total(got, "tune.assemble", "positions")
    real = spans.total(got, "tune.assemble", "real_tokens")
    return 100.0 * (positions - real) / positions if positions else None
