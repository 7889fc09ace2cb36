"""Paper Fig. 13: Adapter Parallelism (AP) vs FSDP multi-LoRA, from the
compiled production-mesh artifacts (this container cannot wall-clock 256
chips; the comparison is the roofline step bound + collective traffic +
per-device memory of the two compiled programs).

The variant lowering runs in a subprocess because it needs the 512-device
host platform (benchmarks themselves stay on 1 device).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit
from repro.roofline.analysis import HBM_BW, ICI_BW, PEAK_FLOPS

OUT = os.path.join(os.path.dirname(__file__), "..", "experiments",
                   "ap_vs_fsdp")
ARCH, SHAPE = "stablelm-3b", "train_4k"


def ensure_artifacts() -> None:
    need = [f"{ARCH}__{SHAPE}__{v}.json" for v in ("ap", "fsdp")]
    if all(os.path.exists(os.path.join(OUT, n)) for n in need):
        return
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # the 512 devices are virtual host-CPU devices: pin the child to the
    # CPU so it never claims an accelerator its parent process may hold
    env["JAX_PLATFORMS"] = "cpu"
    subprocess.run(
        [sys.executable, "-m", "repro.launch.sharding_variants",
         "--arch", ARCH, "--shape", SHAPE],
        check=True, env=env, timeout=900)


def step_bound(rec: dict) -> float:
    return max(rec["flops"] / PEAK_FLOPS, rec["hlo_bytes"] / HBM_BW,
               rec["collective_traffic"] / ICI_BW)


def run() -> None:
    ensure_artifacts()
    recs = {}
    for v in ("ap", "fsdp"):
        with open(os.path.join(OUT, f"{ARCH}__{SHAPE}__{v}.json")) as f:
            recs[v] = json.load(f)
    ap_t, fs_t = step_bound(recs["ap"]), step_bound(recs["fsdp"])
    HBM = 16 * 2 ** 30
    for v, rec in recs.items():
        fits = rec["argument_bytes"] + rec["temp_bytes"] <= HBM
        emit(f"fig13/{v}_step_bound", step_bound(rec),
             f"coll_bytes={rec['collective_traffic']:.3e};"
             f"arg_bytes={rec['argument_bytes']:.3e};fits_hbm={fits}")
    emit("fig13/ap_speedup_vs_fsdp", 0.0,
         f"{fs_t / ap_t:.2f}x_step_bound;"
         f"adapter_mem_ratio="
         f"{recs['fsdp']['argument_bytes'] / max(recs['ap']['argument_bytes'], 1):.1f}x;"
         f"fsdp_oom_at_Z64_r64="
         f"{recs['fsdp']['argument_bytes'] > HBM}")


if __name__ == "__main__":
    run()
