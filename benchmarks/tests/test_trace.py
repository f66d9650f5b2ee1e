"""The trace reduction, on a hand-made trace whose answers are known and on
the small trace recorded on a TPU v5e that is kept beside it
(``lib/sample_trace/``): busy union, idle share, per-program device time, gap
attribution by annotation.
"""
import glob
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from lib.trace import (  # noqa: E402
    TraceSummary, _innermost_segments, load_xplane, op_family, program_name,
)


def hand_made():
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_prog(123)", 10.0, 30.0), ("jit_core(9)", 60.0, 10.0),
                ("jit_core(9)", 150.0, 10.0)]},  # the last one is outside the window
            {"name": "XLA Ops", "events": [
                ("fusion.1", 10.0, 10.0), ("fusion.2", 25.0, 15.0),
                ("fusion.2", 30.0, 5.0),  # overlaps: the union counts it once
                ("while.9", 60.0, 10.0), ("copy.3", 62.0, 6.0),  # a loop and its body
                ("copy.3", 150.0, 10.0)]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ("bench:window", 0.0, 100.0), ("bench:x", 0.0, 50.0),
            ("bench:y", 45.0, 3.0), ("not ours", 0.0, 100.0)]}]},
    ]


def test_names():
    assert program_name("jit_superround(1234567890)") == "jit_superround"
    assert op_family("fusion.123") == "fusion"
    assert op_family("%copy-done.4 = f32[8] copy-done(...)") == "copy-done"


def test_innermost_segments_nest():
    segs = _innermost_segments([("bench:a", 0, 100), ("bench:b", 10, 20),
                                ("bench:c", 30, 50), ("bench:d", 35, 40)])
    assert segs == [(0, 10, "bench:a"), (10, 20, "bench:b"), (20, 30, "bench:a"),
                    (30, 35, "bench:c"), (35, 40, "bench:d"), (40, 50, "bench:c"),
                    (50, 100, "bench:a")]


def test_hand_made_trace():
    t = TraceSummary(hand_made())
    assert t.window_s == pytest.approx(100e-9)
    assert t.busy_s == pytest.approx(35e-9)  # [10,20] + [25,40] + [60,70]
    assert t.program_time("^jit_prog$") == (pytest.approx(30e-9), 1)
    assert t.program_time("^jit_core$") == (pytest.approx(10e-9), 1)
    assert t.program_time("^jit_absent$") == (0.0, 0)
    assert dict(t.device_ops) == {"jit_prog:fusion": pytest.approx(25e-9),
                                  "jit_core:while": pytest.approx(4e-9),
                                  "jit_core:copy": pytest.approx(6e-9)}
    gaps = dict(t.idle_gaps)
    # idle: [0,10] [20,25] [40,60] [70,100]; x covers [0,50] less y's [45,48]
    assert gaps["x"] == pytest.approx(22e-9)
    assert gaps["y"] == pytest.approx(3e-9)
    assert gaps["unannotated"] == pytest.approx(40e-9)
    assert sum(gaps.values()) + t.busy_s == pytest.approx(t.window_s)


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        TraceSummary([p for p in hand_made() if p["name"].startswith("/host")])


def test_recorded_trace():
    found = sorted(glob.glob(os.path.join(BENCH, "lib", "sample_trace", "*.xplane.pb")))
    assert found, "the recorded trace is missing"
    t = TraceSummary(load_xplane(found[0]))
    with open(found[0][: -len(".xplane.pb")] + ".expected.json") as f:
        import json

        want = json.load(f)
    assert t.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert t.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < t.busy_s <= t.window_s
    for name, (seconds, runs) in want["programs"].items():
        got = t.program_time(f"^{name}$")
        assert got[0] == pytest.approx(seconds, rel=1e-9) and got[1] == runs
    assert t.breakdown()["device_ops"][0][0] == want["top_op"]
    gaps = dict(t.idle_gaps)
    assert sum(gaps.values()) + t.busy_s == pytest.approx(t.window_s, rel=1e-6)
    for name in want["gap_names"]:
        assert name in gaps
    # the busy union again, by brute force: a 100 ns raster of the window
    import numpy as np

    lo, hi = t.window_ns
    raster = np.zeros(int((hi - lo) / 100) + 2, dtype=bool)
    for plane in load_xplane(found[0]):
        for line in plane["lines"]:
            if plane["name"].startswith("/device:TPU:") and line["name"] == "XLA Ops":
                for _n, s, d in line["events"]:
                    a, b = max(s, lo), min(s + d, hi)
                    if b > a:
                        raster[int((a - lo) / 100): int(np.ceil((b - lo) / 100))] = True
    assert raster.sum() * 100e-9 == pytest.approx(t.busy_s, rel=0.02)
