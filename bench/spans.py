"""The program's own host spans in the traced window: where the device's
idle time falls among them, and the counts they carry.

The program marks its host work with ``tune.*`` and ``serve.*`` spans
(``jax.profiler.TraceAnnotation``), on the profiler's clock, each with
whole-number counts as the event's stats. The benchmark's own spans around calls into the
program (``BENCH``) are not program spans.

* ``load(path)`` reads an ``.xplane.pb`` into a plain dict (nanoseconds,
  one clock): ``{"device": {id: [[start, dur], ...]}, "host": [[name,
  start, dur, {stat: value}], ...]}`` -- each device's "XLA Ops"
  intervals, and the host spans named ``bench.``, ``tune.`` or
  ``serve.``. It keeps what it read, by path, for the other readers of
  the same run.
* ``split(events, chips)`` works over the ``bench.window`` span. Each
  instant of each idle gap of the device (no op running) goes to the
  innermost program span open at that instant (the shortest one covering
  it), or to ``unspanned``; a chip's gaps are averaged over the chips. The
  stats of the program spans that start in the window are summed by name.
* ``of_run(run, ctx)`` is ``split`` of the run's own trace, where the
  harness wrote it, and None where the window holds no TPU device plane
  or no program span (a program without spans).
"""
from __future__ import annotations

import bisect
import functools
import heapq
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

from bench import harness, trace

UNSPANNED = "unspanned"
# the benchmark's own spans, around its calls into the program
BENCH = frozenset({trace.WINDOW, "tune.chunk", "tune.train_step",
                   "tune.eval_step", "serve.submit", "serve.step",
                   "serve.wait"})
# the executor's spans, by the part of its host work each one times
TUNE_FEED = ("tune.assemble", "tune.loss_fetch", "tune.observe",
             "tune.report")
TUNE_EVAL = ("tune.eval", "tune.eval_fetch", "tune.decide")
TUNE_SLOTS = ("tune.best_ckpt", "tune.snapshot", "tune.admit",
              "tune.restore", "tune.evict")
_PREFIXES = ("bench.", "tune.", "serve.")


def is_program(name: str) -> bool:
    return name.startswith(("tune.", "serve.")) and name not in BENCH


@functools.lru_cache(maxsize=1)
def load(path: str) -> Dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: Dict[str, List[List[int]]] = {}
    host: List[List] = []
    for plane in data.planes:
        m = trace._DEVICE.match(plane.name)
        if m:
            device[m.group(1)] = [
                [int(e.start_ns), int(e.duration_ns)]
                for line in plane.lines if line.name == "XLA Ops"
                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(_PREFIXES):
                        host.append([e.name, int(e.start_ns),
                                     int(e.duration_ns),
                                     {k: v for k, v in e.stats
                                      if isinstance(v, (int, float))}])
    return {"device": device, "host": host}


def innermost(spans: Sequence[Tuple[str, int, int]]
              ) -> List[Tuple[int, int, str]]:
    """Cut time at every start and end of ``spans`` ((name, start, end))
    into pieces, each labelled with the shortest span covering it (of
    equal ones, the first listed); pieces no span covers are left out.
    Sorted by start."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    open_: List[Tuple[int, int, int]] = []      # (length, index, end)
    out: List[Tuple[int, int, str]] = []
    nxt = 0
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(order) and spans[order[nxt]][1] <= a:
            i = order[nxt]
            heapq.heappush(open_, (spans[i][2] - spans[i][1], i,
                                   spans[i][2]))
            nxt += 1
        while open_ and open_[0][2] <= a:
            heapq.heappop(open_)
        if open_:
            name = spans[open_[0][1]][0]
            if out and out[-1][2] == name and out[-1][1] == a:
                out[-1] = (out[-1][0], b, name)
            else:
                out.append((a, b, name))
    return out


def attribute(gaps: Sequence[Tuple[int, int]],
              pieces: Sequence[Tuple[int, int, str]]) -> Dict[str, int]:
    """Nanoseconds of ``gaps`` under each label of ``pieces`` (disjoint,
    sorted), and under ``unspanned`` where no piece lies."""
    starts = [p[0] for p in pieces]
    out: Dict[str, int] = {}
    for lo, hi in gaps:
        covered = 0
        i = max(bisect.bisect_right(starts, lo) - 1, 0)
        while i < len(pieces) and pieces[i][0] < hi:
            s, e, name = pieces[i]
            got = min(e, hi) - max(s, lo)
            if got > 0:
                out[name] = out.get(name, 0) + got
                covered += got
            i += 1
        if hi - lo > covered:
            out[UNSPANNED] = out.get(UNSPANNED, 0) + hi - lo - covered
    return out


def split(events: Dict, chips: int) -> Optional[Dict]:
    """Idle seconds by innermost program span (``idle``, with their total
    ``idle_s`` and the window's length ``window_s``), and for each program
    span that starts in the window its stats (``each``: name -> list of
    stat dicts) and their sums with the span count (``sums``: name ->
    {"spans": n, stat: total}). None where the trace holds no TPU device
    plane."""
    ids = sorted(events["device"], key=int)[:chips]
    if not ids:
        return None
    windows = [(s, s + d) for n, s, d, _ in events["host"]
               if n == trace.WINDOW]
    if not windows:
        raise ValueError("the trace holds no window span")
    lo, hi = max(windows, key=lambda iv: iv[1] - iv[0])
    program = [(n, s, s + d) for n, s, d, _ in events["host"]
               if is_program(n)]
    pieces = innermost(program)
    idle: Dict[str, float] = {}
    for i in ids:
        ivs = [(s, s + d) for s, d in events["device"][i]]
        busy = trace.clip(trace.union(ivs), lo, hi)
        for name, ns in attribute(trace.gaps(busy, lo, hi), pieces).items():
            idle[name] = idle.get(name, 0.0) + ns / len(ids) / 1e9
    each: Dict[str, List[Dict]] = {}
    for n, s, _, stats in events["host"]:
        if is_program(n) and lo <= s < hi:
            each.setdefault(n, []).append(stats)
    sums = {n: {"spans": len(v),
                **{k: sum(x.get(k, 0) for x in v)
                   for k in sorted({k for x in v for k in x})}}
            for n, v in each.items()}
    return {"window_s": (hi - lo) / 1e9, "idle_s": sum(idle.values()),
            "idle": idle, "each": each, "sums": sums}


def of_run(run, ctx) -> Optional[Dict]:
    """``split`` of the run's traced window (read once a process; the time
    the first read took is ``span_read_s`` among the run's counters), or
    None where there is nothing to read."""
    if not ctx.trace:
        return None
    path = harness._xplane(os.path.join(harness.OUT, "trace", ctx.workload))
    t0 = time.perf_counter()
    got = _split_file(path, ctx.chips)
    run.counters.setdefault("span_read_s", time.perf_counter() - t0)
    return got if got is not None and got["sums"] else None


@functools.lru_cache(maxsize=1)
def _split_file(path: str, chips: int) -> Optional[Dict]:
    return split(load(path), chips)


def idle_share(got: Optional[Dict], names: Sequence[str]) -> Optional[float]:
    """Idle time under the named spans, in percent of the window."""
    if got is None or not got["window_s"]:
        return None
    return 100.0 * sum(got["idle"].get(n, 0.0) for n in names) \
        / got["window_s"]


def total(got: Dict, name: str, stat: str) -> int:
    return got["sums"].get(name, {}).get(stat, 0)
