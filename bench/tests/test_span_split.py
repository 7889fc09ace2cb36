"""The split of idle time among the program's spans, on two small event
dicts whose numbers are worked out by hand (times in ns).

``tune``: the window (bench.window) is 1000-11000. Chip 0 runs ops at
1000-2000, 4000-5000 and 8000-9000: 3000 ns busy, idle gaps 2000-4000,
5000-8000 and 9000-11000 (7000 ns, 70% of the window). tune.chunk, the
benchmark's own span, covers the whole window.
- 2000-4000 straddles tune.assemble (1500-3000) and tune.loss_fetch
  (3000-4500): 1000 ns each.
- 5000-8000 lies in tune.eval (5000-8000), with tune.eval_fetch
  (5500-6500) nested in it: the inner span takes its 1000 ns, tune.eval
  the other 2000.
- 9000-11000 lies under no program span (tune.chunk and the benchmark's
  tune.train_step at 9000-9200 are not program spans): 2000 ns unspanned.
So idle_feed 20%, idle_eval 30%, idle_slots 0%, idle_unspanned 20%, which
sum to idle_share 70%. Stats of spans that start in the window are
summed: two tune.assemble spans (the second, 4200-4800, lies in busy
time) give real_tokens 228 of 256 positions, pad share 28/256 =
10.9375%; the d2h bytes are 8 + 8 over 2 steps, 8e-6 MB a step.
tune.snapshot (0-900) starts before the window and is not counted. Chip
1 is busy the whole window, so over two chips each idle part halves.

``serve``: window 0-10000, ops at 0-4000 and 6000-8000, gaps 4000-6000
and 8000-10000. In 4000-6000, inside the benchmark's serve.step:
serve.fill 500, serve.join 470 and its three nested serve.request events
30, serve.dispatch 500, serve.token_fetch 500: 2000 ns of program spans,
idle_host 20%. 8000-10000 is the client's serve.wait: unspanned. Lane
occupancy (5 + 3) / (8 + 8) = 50%; queue waits 15, 100 and 40 ms, whose
90th percentile (linear) is 40 + 0.8 x 60 = 88.
"""
import json
import os

import pytest

from bench import harness, spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture
def events():
    with open(os.path.join(DATA, "spans_small.json")) as f:
        return json.load(f)


def as_trace(ev):
    """The same events in ``bench/trace.py``'s form."""
    return {"device": {i: {"ops": [["op", s, d] for s, d in ops],
                           "modules": []}
                       for i, ops in ev["device"].items()},
            "host": [h[:3] for h in ev["host"]]}


def reader(name):
    return harness.load_module(
        os.path.join(harness.BENCH, "metrics", name + ".py"),
        "bench_metric_" + name.replace(".", "_"))


def test_split_one_chip(events):
    got = spans.split(events["tune"], chips=1)
    assert got["window_s"] == pytest.approx(10000e-9)
    assert got["idle"] == pytest.approx({
        "tune.assemble": 1000e-9, "tune.loss_fetch": 1000e-9,
        "tune.eval": 2000e-9, "tune.eval_fetch": 1000e-9,
        "unspanned": 2000e-9})
    assert got["sums"] == {
        "tune.assemble": {"spans": 2, "positions": 256, "real_tokens": 228},
        "tune.loss_fetch": {"spans": 1, "d2h_bytes": 8},
        "tune.eval": {"spans": 1},
        "tune.eval_fetch": {"spans": 1, "d2h_bytes": 8}}


@pytest.mark.parametrize("chips", [1, 2])
def test_parts_add_up_to_the_idle_total(events, chips):
    got = spans.split(events["tune"], chips)
    t = trace.reduce(as_trace(events["tune"]), chips)
    assert got["idle_s"] == pytest.approx(t["window_s"] - t["busy_s"])
    assert got["idle"]["tune.eval"] == pytest.approx(2000e-9 / chips)


def test_tune_readers(events, monkeypatch):
    """The four idle parts sum to idle_share.tune as its own reader gives
    it; the counts read as worked out above."""
    run = harness.Run(trace=trace.reduce(as_trace(events["tune"]), 1))
    got = spans.split(events["tune"], 1)
    monkeypatch.setattr(spans, "of_run", lambda run, ctx: got)
    parts = {n: reader(n).read(run, None) for n in (
        "idle_feed.tune", "idle_eval.tune", "idle_slots.tune",
        "idle_unspanned.tune")}
    assert parts == pytest.approx({
        "idle_feed.tune": 20.0, "idle_eval.tune": 30.0,
        "idle_slots.tune": 0.0, "idle_unspanned.tune": 20.0})
    assert sum(parts.values()) == pytest.approx(
        reader("idle_share.tune").read(run, None))
    assert reader("assemble_pad_share.tune").read(run, None) == \
        pytest.approx(100.0 * 28 / 256)
    assert reader("d2h_mb_per_step.tune").read(run, None) == \
        pytest.approx(8e-6)


def test_serve_readers(events, monkeypatch):
    got = spans.split(events["serve"], 1)
    assert got["idle"] == pytest.approx({
        "serve.fill": 500e-9, "serve.join": 470e-9,
        "serve.request": 30e-9, "serve.dispatch": 500e-9,
        "serve.token_fetch": 500e-9, "unspanned": 2000e-9})
    monkeypatch.setattr(spans, "of_run", lambda run, ctx: got)
    run = harness.Run()
    assert reader("idle_host.serve").read(run, None) == pytest.approx(20.0)
    assert reader("lane_occupancy.serve").read(run, None) == \
        pytest.approx(50.0)
    assert reader("queue_wait_p90_ms.serve").read(run, None) == \
        pytest.approx(88.0)


def test_families_partition_the_executor_spans():
    fams = [spans.TUNE_FEED, spans.TUNE_EVAL, spans.TUNE_SLOTS]
    names = [n for f in fams for n in f]
    assert len(names) == len(set(names)) == 12
    assert all(spans.is_program(n) for n in names)
    assert not any(spans.is_program(n) for n in spans.BENCH)


def test_nothing_to_read(events, monkeypatch):
    """No TPU plane gives None; a window with no program span (a program
    that has none) reads as nothing, so its metrics are left out."""
    events["tune"]["device"] = {}
    assert spans.split(events["tune"], 1) is None

    class Ctx:
        trace, workload, chips = True, "cell", 1

    bare = {"device": {"0": [[1000, 1000]]},
            "host": [["bench.window", 1000, 10000, {}],
                     ["tune.chunk", 1000, 10000, {}]]}
    monkeypatch.setattr(harness, "_xplane", lambda tdir: "trace.xplane.pb")
    monkeypatch.setattr(spans, "load", lambda path: bare)
    spans._split_file.cache_clear()
    run = harness.Run()
    assert spans.of_run(run, Ctx()) is None
    assert "span_read_s" in run.counters
    assert reader("idle_unspanned.tune").read(run, Ctx()) is None
    spans._split_file.cache_clear()


def test_innermost():
    """Overlapping spans that do not nest: the shorter one covering each
    instant wins (c, 3-14, is longer than a, 0-10, so a keeps 4-10)."""
    pieces = spans.innermost([("a", 0, 10), ("b", 2, 4), ("c", 3, 14)])
    assert pieces == [(0, 2, "a"), (2, 4, "b"), (4, 10, "a"),
                      (10, 14, "c")]
    assert spans.attribute([(1, 3), (11, 16)], pieces) == {
        "a": 1, "b": 1, "c": 3, "unspanned": 2}
