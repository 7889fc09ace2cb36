"""The trace reduction on a small trace whose numbers are worked out by
hand (times in ns; the window is the host span bench.window, 1000-11000).

Chip 0's ops cover 500-1500, 2000-3000 and 2500-4000 (overlapping),
7000-8000 and 10500-11500. Clipped to the window their union is
1000-1500, 2000-4000, 7000-8000 and 10500-11000: 4000 ns busy. The idle
gaps are 1500-2000 (500, inside tune.chunk 1000-5000), 4000-7000 (3000:
1000 under tune.chunk, 2000 under serve.wait 5000-11000, so serve.wait)
and 8000-10500 (2500, serve.wait). train_step runs 500-4000 and
10500-11500: 3000 + 500 = 3500 ns in the window, two launches; eval_step
1000 ns, one launch. Chip 1 is busy the whole window.
"""
import json
import os

import pytest

from bench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture
def events():
    with open(os.path.join(DATA, "trace_small.json")) as f:
        return json.load(f)


def test_one_chip(events):
    r = trace.reduce(events, chips=1)
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(4000e-9)
    assert r["modules"]["train_step"] == pytest.approx([3500e-9, 2])
    assert r["modules"]["eval_step"] == pytest.approx([1000e-9, 1])
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"serve.wait": 5500e-9, "tune.chunk": 500e-9})
    ops = dict(r["device_ops"])
    assert ops == pytest.approx({
        "%fusion.3 (kOutput)": 1500e-9, "%convolution.2": 1000e-9,
        "%fusion.4 (kLoop)": 1000e-9, "%fusion.1 (kLoop)": 500e-9,
        "%copy.5": 500e-9})
    assert r["device_ops"][0][0] == "%fusion.3 (kOutput)"


def test_two_chips_average(events):
    r = trace.reduce(events, chips=2)
    assert r["busy_s"] == pytest.approx((4000e-9 + 10000e-9) / 2)
    assert r["modules"]["train_step"] == pytest.approx(
        [(3500e-9 + 10000e-9) / 2, 1.5])
    assert dict(r["idle_gaps"])["serve.wait"] == pytest.approx(5500e-9 / 2)


def test_no_device_plane_gives_none(events):
    events["device"] = {}
    assert trace.reduce(events, chips=1) is None


def test_names():
    assert trace.module_name("jit_ranked_decode_lanes(3)") == \
        "ranked_decode_lanes"
    assert trace.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert trace.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]
