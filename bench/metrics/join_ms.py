"""Device milliseconds per join launch: the fused join-and-decode
(``ranked_join_decode``) and the separate lane prefill
(``ranked_lane_prefill``) modules together, over the traced window."""

MODULES = ("ranked_join_decode", "ranked_lane_prefill")


def read(run, ctx):
    if run.trace is None:
        return None
    got = [run.trace["modules"][m] for m in MODULES
           if m in run.trace["modules"]]
    launches = sum(n for _, n in got)
    return 1e3 * sum(s for s, _ in got) / launches if launches else None
