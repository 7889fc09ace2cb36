"""Padding token positions over all token positions of the fused batches
trained in the window (Z x b_cap x S_cap per step), in percent: a count
made at the train-step call, real tokens from each resident slot's width."""


def read(run, ctx):
    c = run.counters
    if not c.get("positions"):
        return None
    return 100.0 * (c["positions"] - c["real_tokens"]) / c["positions"]
