"""Device milliseconds per launch of the jitted train step
(``train_step`` module in the trace), over the traced window."""


def read(run, ctx):
    if run.trace is None or "train_step" not in run.trace["modules"]:
        return None
    seconds, launches = run.trace["modules"]["train_step"]
    return 1e3 * seconds / launches if launches else None
