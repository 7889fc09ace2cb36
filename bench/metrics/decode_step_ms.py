"""Device milliseconds per decode-only launch (``ranked_decode_lanes``
module in the trace), over the traced window."""


def read(run, ctx):
    if run.trace is None or "ranked_decode_lanes" not in run.trace["modules"]:
        return None
    seconds, launches = run.trace["modules"]["ranked_decode_lanes"]
    return 1e3 * seconds / launches if launches else None
