"""Shared machinery of the benchmark: cell lookup, the model configuration
as run, the device check, the compile counter, the traced window, the
per-layer metric readers and the result line.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by name:

* ``bench/configs/<config>.json``  -- the configuration's sizes;
* ``bench/traffic/<traffic>.json`` -- the traffic mix, whose ``driver``
  key names ``bench/drivers/<driver>.py``;
* ``bench/metrics/<metric>.py``    -- a per-layer metric's reader;
* ``bench/limits/<workload>.json`` -- the limits of the cell's correctness
  comparison (under ``rehearse``, those of the CPU rehearsal's sizes).
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(ROOT, ".bench_out")      # traces; git-ignored

# ModelConfig field <- key of the configuration file (the names of the
# published config.json)
FIELDS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "norm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}

# the CPU rehearsal's sizes: the same code paths at a size the CPU runs in
# seconds (head count and GQA grouping kept in ratio)
REHEARSAL = {"num_hidden_layers": 2, "hidden_size": 128, "head_dim": 32,
             "intermediate_size": 256, "vocab_size": 512}


class BenchError(Exception):
    """The run cannot produce a result (no chip, unknown cell, ...)."""


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise BenchError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# configuration
# ---------------------------------------------------------------------------

def rehearsal_spec(spec: Dict) -> Dict:
    """A tiny configuration with the same keys, for the CPU rehearsal."""
    out = dict(spec)
    out.update(REHEARSAL)
    group = spec["num_attention_heads"] // spec["num_key_value_heads"]
    out["num_attention_heads"] = 4
    out["num_key_value_heads"] = max(4 // group, 1)
    return out


def model_config(spec: Dict, reduced: List[str], rehearse: bool = False):
    """The program's ``ModelConfig`` for a configuration file: the
    registered architecture with the file's sizes. Outside a rehearsal a
    size that differs from the registered one must be listed in
    ``reduced``, so the file and the program cannot drift apart."""
    from repro.configs.registry import get_arch
    base = get_arch(spec["arch"])
    want = {}
    for key, field in FIELDS.items():
        if key in spec:
            want[field] = spec[key]
    want["rope"] = dataclasses.replace(base.rope, theta=spec["rope_theta"])
    lora = spec["lora"]
    want["lora"] = dataclasses.replace(
        base.lora, r_max=lora["r_max"], targets=tuple(lora["targets"]),
        alpha_over_r=lora["alpha_over_r"])
    if not rehearse:
        by_field = {v: k for k, v in FIELDS.items()}
        for field, value in want.items():
            have = getattr(base, field)
            if field == "head_dim":
                have = base.resolved_head_dim
            if have != value and by_field.get(field, field) not in reduced:
                raise BenchError(
                    f"{spec['name']}: {field} is {value} in the file and "
                    f"{have} in the registered {spec['arch']}, and "
                    f"{by_field.get(field, field)} is not in reduced")
    cfg = dataclasses.replace(base, **want)
    cfg.validate()
    return cfg


def reference(spec: Dict):
    """The plain reference module the configuration names
    (``bench/reference/<reference>.py``; the dense decoder by default)."""
    name = spec.get("reference", "decoder")
    return load_module(os.path.join(BENCH, "reference", name + ".py"),
                       "bench_reference_" + name)


# ---------------------------------------------------------------------------
# compile counter
# ---------------------------------------------------------------------------

class CompileCounter:
    """Counts lowerings to XLA (every jit-cache miss: a compile or a load
    from the persistent cache) while armed."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        from jax import monitoring
        self.names: List[str] = []
        self.armed = False

        def listen(event: str, duration: float, **kw) -> None:
            if self.armed and event == self.EVENT:
                self.names.append(str(kw.get("fun_name")))

        monitoring.register_event_duration_secs_listener(listen)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What a driver hands back: end-to-end values, the counters and the
    traced window that per-layer readers read, and the checks."""
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)
    counters: Dict[str, float] = dataclasses.field(default_factory=dict)
    checks: List[Dict] = dataclasses.field(default_factory=list)
    control_checks: List[Dict] = dataclasses.field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    trace: Optional[Dict] = None           # bench/trace.py reduction
    memory_peak_bytes: Optional[int] = None

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append({"name": name, "value": float(value),
                            "limit": float(limit)})

    def check_control(self, name: str, value: float, limit: float) -> None:
        """A number of the control (the reference in the precision below
        the configuration's, in the program's place), held to the same
        limit as the program's."""
        self.control_checks.append({"name": name, "value": float(value),
                                    "limit": float(limit)})

    @property
    def correct(self) -> bool:
        return verdict(self.checks)

    @property
    def control_correct(self) -> Optional[bool]:
        return verdict(self.control_checks) if self.control_checks else None


def verdict(checks: List[Dict]) -> bool:
    """``correct``: there are numbers compared, and each is within its
    limit."""
    return bool(checks) and all(c["value"] <= c["limit"] for c in checks)


# the control's precision: the nearest below the one the configuration
# states (``torch_dtype``), as ``bench/reference/<module>.py`` computes it
CONTROL = {"bfloat16": "fp8"}


class Context:
    """One cell's run: its configuration, traffic, limits and options."""

    def __init__(self, bench: Dict, workload: str, seed: int,
                 seconds: float, trace: bool, rehearse: bool = False):
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise BenchError(f"unknown workload {workload!r}; have "
                             f"{sorted(cells)}")
        self.bench = bench
        self.cell = cells[workload]
        self.workload = workload
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.cell["config"]]
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.rehearse = rehearse
        spec = load_json(ROOT, self.config_entry["file"])
        self.spec = rehearsal_spec(spec) if rehearse else spec
        self.traffic = load_json(BENCH, "traffic",
                                 self.cell["traffic"] + ".json")
        if rehearse:
            self.traffic = {**self.traffic,
                            **self.traffic.get("rehearse", {})}
        self.limits = load_json(BENCH, "limits", workload + ".json")
        if rehearse:
            self.limits = {**self.limits, **self.limits["rehearse"]}
        self.cfg = model_config(self.spec, self.config_entry["reduced"],
                                rehearse)
        self.compiles = CompileCounter()
        self.control = None     # the control's quant (use_control)
        self.detail = False     # bench/calibrate.py: keep every value read

    def use_control(self) -> None:
        """Also run the control after the window and hold it to the
        cell's limits (``bench/calibrate.py``, the control's test); the
        benchmark's own runs never do."""
        dtype = self.spec["torch_dtype"]
        if dtype not in CONTROL:
            raise BenchError(f"no control precision below {dtype}")
        self.control = CONTROL[dtype]

    def device(self):
        import jax
        return jax.devices()[0]

    @contextlib.contextmanager
    def window(self, run: Run):
        """The measured window: the compile counter is armed, and with
        ``--trace 1`` the profiler records it. Yields nothing; the driver
        times its own window by the host clock."""
        import jax
        tdir = os.path.join(OUT, "trace", self.workload)
        if self.trace:
            _clear(tdir)
            jax.profiler.start_trace(tdir)
        self.compiles.names = []
        self.compiles.armed = True
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            self.compiles.armed = False
            run.counters["compiles"] = len(self.compiles.names)
            run.counters["compiled"] = sorted(set(self.compiles.names))
            if self.trace:
                jax.profiler.stop_trace()
        if self.trace:
            from bench.trace import reduce_file
            t0 = time.perf_counter()
            run.trace = reduce_file(_xplane(tdir), self.chips)
            run.counters["trace_read_s"] = time.perf_counter() - t0
            if run.trace is None and not self.rehearse:
                raise BenchError("the trace holds no TPU device plane")

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    def read_peak(self, run: Run) -> None:
        """Peak device memory so far, on the fullest chip used; read after
        the window and before the reference runs."""
        import jax
        peaks = []
        for d in jax.devices()[:self.chips]:
            stats = d.memory_stats() or {}
            if "peak_bytes_in_use" in stats:
                peaks.append(int(stats["peak_bytes_in_use"]))
        run.memory_peak_bytes = max(peaks) if peaks else None


def _clear(path: str) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)
    os.makedirs(path, exist_ok=True)


def _xplane(tdir: str) -> str:
    found = []
    for dirpath, _, files in os.walk(tdir):
        found += [os.path.join(dirpath, f) for f in files
                  if f.endswith(".xplane.pb")]
    if not found:
        raise BenchError(f"the profiler wrote no trace under {tdir}")
    return max(found, key=os.path.getmtime)


def free() -> None:
    """Drop unreferenced device buffers before the reference runs."""
    gc.collect()


def per_layer_metrics(ctx: Context, run: Run) -> Dict[str, Dict]:
    """Each per-layer metric of this cell, read by its own reader in
    ``bench/metrics/<name>.py``; a reader that finds nothing returns None
    and the metric is left out."""
    reported = set(e2e_names(ctx))
    out: Dict[str, Dict] = {}
    for m in ctx.bench["per_layer"]:
        cells = m.get("workloads")
        if cells is not None and ctx.workload not in cells:
            continue
        if cells is None and m["moves"] not in reported:
            continue
        reader = load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"),
                             "bench_metric_" + m["name"].replace(".", "_"))
        value = reader.read(run, ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def e2e_names(ctx: Context) -> List[str]:
    return [m["name"] for m in ctx.bench["end_to_end"]
            if ctx.workload in m.get("workloads", [ctx.workload])]


def check_device(ctx: Context) -> Dict:
    """Platform, kind and count as JAX reports them. No TPU, or fewer
    chips than the cell asks for, is an error (the rehearsal excepted)."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if not ctx.rehearse and info["platform"] != "tpu":
        raise BenchError(f"no TPU: JAX found {info['platform']}")
    if info["count"] < ctx.chips:
        raise BenchError(f"the cell needs {ctx.chips} chips, JAX found "
                         f"{info['count']}")
    return info


def use_compile_cache(rehearse: bool = False) -> None:
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` when it is
    set, else a fixed directory in the checkout. Every program is cached,
    however quick its compile, so a warm set-up loads and never
    compiles. The CPU rehearsal caches nothing."""
    import jax
    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
        return
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def result_line(ctx: Context, run: Run, device: Dict,
                setup_s: float) -> Dict:
    """The last line of standard output."""
    run.e2e["setup_s"] = setup_s
    run.counters["setup_s"] = setup_s
    units = {m["name"]: m["unit"] for m in ctx.bench["end_to_end"]}
    if ctx.trace:
        metrics = per_layer_metrics(ctx, run)
    else:
        metrics = {n: {"value": run.e2e[n], "unit": units[n]}
                   for n in e2e_names(ctx)}
    device = dict(device)
    device["memory_peak_bytes"] = run.memory_peak_bytes
    out: Dict[str, Any] = {
        "correct": run.correct,
        "attempted": run.attempted, "failed": run.failed,
        "metrics": metrics, "device": device,
    }
    if ctx.trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        out["breakdown"] = {"device_ops": run.trace["device_ops"][:10],
                            "idle_gaps": run.trace["idle_gaps"][:10]}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in run.checks}
    return out


def print_checks(run: Run) -> None:
    """Each compared number beside its limit, as the last lines of
    standard error."""
    for c in run.checks:
        verdict = "ok" if c["value"] <= c["limit"] else "FAIL"
        print(f"check {c['name']}: {c['value']!r} limit {c['limit']!r} "
              f"{verdict}", file=sys.stderr, flush=True)


def clock() -> float:
    return time.perf_counter()


def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)
