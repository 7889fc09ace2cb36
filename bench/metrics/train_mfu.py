"""Model FLOPs of the tokens trained in the window (forward and the frozen
backbone's activation gradients, LoRA at each slot's true rank; no remat,
no padding; ``bench/flops.py``) over the window times the chips times
the device's bf16 peak (``bench/peaks.json``), in percent."""
from bench import flops


def read(run, ctx):
    c = run.counters
    if run.trace is None or not c.get("model_flops") or not c.get("window_s"):
        return None
    peak = flops.peak(ctx.device().device_kind)
    return 100.0 * c["model_flops"] / (c["window_s"] * c["chips"] * peak)
