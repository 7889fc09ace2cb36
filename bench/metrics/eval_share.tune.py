"""Device time of the jitted eval step (``eval_step`` module) over device
busy time in the traced window, in percent. The window holds whole chunk
cycles (train steps and the eval that ends them)."""


def read(run, ctx):
    t = run.trace
    if t is None or not t["busy_s"] or "eval_step" not in t["modules"]:
        return None
    return 100.0 * t["modules"]["eval_step"][0] / t["busy_s"]
