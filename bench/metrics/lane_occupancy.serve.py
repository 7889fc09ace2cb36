"""Lanes holding a request over all lanes, summed over the decode and
join-and-decode launches of the traced window (the ``active_lanes`` and
``lanes`` counts of ``serve.dispatch``), in percent."""
from bench import spans


def read(run, ctx):
    got = spans.of_run(run, ctx)
    if got is None:
        return None
    lanes = spans.total(got, "serve.dispatch", "lanes")
    active = spans.total(got, "serve.dispatch", "active_lanes")
    return 100.0 * active / lanes if lanes else None
