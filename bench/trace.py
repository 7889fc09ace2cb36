"""Reduction of a profiler trace to the benchmark's device numbers.

Two steps, so the second can be checked on a small recorded trace:

* ``load_xplane(path)`` reads the ``.xplane.pb`` the JAX profiler wrote
  into a plain dict of events (nanoseconds, one clock):
  ``{"device": {id: {"ops": [[name, start, dur]...],
  "modules": [[name, start, dur]...]}}, "host": [[name, start, dur]...]}``
  -- the device planes' "XLA Ops" and "XLA Modules" lines, and the host
  spans the benchmark annotates (names with a dot, such as
  ``bench.window`` or ``tune.chunk``).
* ``reduce(events, chips)`` computes, over the ``bench.window`` span:
  busy time as the union of device op intervals (averaged over the
  chips), device time and launch count per jitted module, the device ops
  that took most time, and the idle time attributed to the host span
  that covers most of each gap.
"""
from __future__ import annotations

import re
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[int, int]

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


def load_xplane(path: str) -> Dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: Dict[str, Dict[str, List]] = {}
    host: List[List] = []
    for plane in data.planes:
        m = _DEVICE.match(plane.name)
        if m:
            lines = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key is None:
                    continue
                lines[key] = [[e.name, int(e.start_ns), int(e.duration_ns)]
                              for e in line.events]
            device[m.group(1)] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if "." in e.name and " " not in e.name \
                            and e.name.split(".")[0] in _HOST_PREFIXES:
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns)])
    return {"device": device, "host": host}


_HOST_PREFIXES = ("bench", "tune", "serve")


def module_name(name: str) -> str:
    """``jit_train_step(12)`` -> ``train_step``."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def op_name(name: str) -> str:
    """An HLO op event is named by its whole instruction
    (``%fusion.12 = bf16[...] fusion(...), ...``): keep the instruction's
    name and, after it, its kind where one is given."""
    head = name.split(" = ", 1)
    if len(head) < 2:
        return name[:80]
    kind = re.search(r"kind=(\w+)", head[1])
    return head[0] + (f" ({kind.group(1)})" if kind else "")


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _window(events: Dict) -> Interval:
    spans = [(s, s + d) for n, s, d in events["host"] if n == WINDOW]
    if spans:
        return max(spans, key=lambda iv: iv[1] - iv[0])
    allev = [(s, s + d) for dev in events["device"].values()
             for _, s, d in dev["ops"]]
    if not allev:
        raise ValueError("trace holds no window span and no device op")
    return min(s for s, _ in allev), max(e for _, e in allev)


def _overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def attribute(gap: Interval, spans: Sequence[Tuple[str, Interval]]
              ) -> str:
    """The host span that covers most of the gap; among equal covers the
    shortest (innermost) span. ``idle`` where no span covers it."""
    best: Optional[Tuple[int, int, str]] = None
    for name, iv in spans:
        ov = _overlap(gap, iv)
        if ov <= 0:
            continue
        key = (ov, -(iv[1] - iv[0]), name)
        if best is None or key[:2] > best[:2]:
            best = key
    return best[2] if best else "idle"


def reduce(events: Dict, chips: int) -> Optional[Dict]:
    """Device numbers of the traced window; times in seconds. None where
    the trace holds no TPU plane."""
    lo, hi = _window(events)
    ids = sorted(events["device"], key=int)[:chips]
    if not ids:
        return None                     # no TPU (the CPU rehearsal)
    spans = [(n, (s, s + d)) for n, s, d in events["host"] if n != WINDOW]
    busy_ns = 0
    modules: Dict[str, List[float]] = {}
    ops: Dict[str, float] = {}
    idle: Dict[str, float] = {}
    for i in ids:
        dev = events["device"][i]
        ivs = [(s, s + d) for _, s, d in dev["ops"]]
        busy = clip(union(ivs), lo, hi)
        busy_ns += sum(e - s for s, e in busy)
        for name, s, d in dev["ops"]:
            got = _overlap((s, s + d), (lo, hi))
            if got:
                name = op_name(name)
                ops[name] = ops.get(name, 0.0) + got
        for name, s, d in dev["modules"]:
            if _overlap((s, s + d), (lo, hi)) <= 0:
                continue
            m = modules.setdefault(module_name(name), [0.0, 0])
            m[0] += _overlap((s, s + d), (lo, hi))
            m[1] += 1
        for g in gaps(busy, lo, hi):
            label = attribute(g, spans)
            idle[label] = idle.get(label, 0.0) + (g[1] - g[0])
    n = len(ids)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_ns / n / 1e9,
        "modules": {k: [v[0] / n / 1e9, v[1] / n]
                    for k, v in modules.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[k, v / n / 1e9] for k, v in
                      sorted(idle.items(), key=lambda kv: -kv[1])[:10]],
    }


def reduce_file(path: str, chips: int) -> Optional[Dict]:
    return reduce(load_xplane(path), chips)
