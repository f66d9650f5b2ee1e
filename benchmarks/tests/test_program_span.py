"""The reader of the program's own spans (``readers/program_span.py``) on a
hand-made record whose answers are known, and a traced rehearsal of each
cell: every per-layer metric that reads the spans is printed, and the parts
add up to the benchmark's own span around the same call. CPU, tiny sizes:
no number here is a device number.
"""
import json
import os
import subprocess
import sys
from collections import namedtuple
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

from readers import program_span  # noqa: E402

R = namedtuple("R", "name start end span_id parent_id wave")


def hand_made():
    """Two lone edits inside the window (10..30), one before it and one that
    straddles its end; the second edit's flush ran a cascade of its own."""
    return [
        R("cascade", 1.0, 2.0, 1, None, 1),  # before the window
        R("lat.dispatch", 1.2, 1.4, 2, 1, 1),
        # edit A: 10..14
        R("wave.profile", 10.0, 10.5, 4, 3, 2),
        R("lat.dispatch", 11.0, 12.0, 6, 5, 2),
        R("lat.readback", 12.0, 13.0, 7, 5, 2),
        R("wave.union", 10.5, 13.5, 5, 3, 2),
        R("cascade", 10.0, 14.0, 3, None, 2),
        # edit B: 20..29, with a flush (20..24) holding an icasc wave
        R("flush.replay.icasc", 20.0, 20.5, 10, 9, None),
        R("lat.dispatch", 21.0, 21.5, 13, 12, 3),
        R("topo.dispatch", 21.5, 22.0, 14, 12, 3),
        R("topo.readback", 22.0, 23.0, 15, 12, 3),
        R("wave.union", 21.0, 23.0, 12, 11, 3),
        R("flush.icasc", 20.5, 23.5, 11, 9, 3),
        R("flush", 20.0, 24.0, 9, 8, None),
        R("lat.dispatch", 25.0, 27.0, 17, 16, 4),
        R("wave.union", 24.0, 28.0, 16, 8, 4),
        R("cascade", 20.0, 29.0, 8, None, 4),
        # straddles the window's end: left out whole
        R("cascade", 29.5, 31.0, 18, None, 5),
    ]


def term(spans, stat="seconds", **kw):
    return dict({"spans": spans, "stat": stat}, **kw)


@pytest.fixture
def index():
    return program_span.SpanIndex(hand_made(), (10.0, 30.0))


@pytest.mark.parametrize("t,want", [
    (term(["cascade"]), 4.0 + 9.0),  # the window filter: two of the four
    (term(["cascade"], "count"), 2),
    (term(["cascade"], "self"), (4.0 - 0.5 - 3.0) + (9.0 - 4.0 - 4.0)),
    (term(["lat.dispatch"]), 1.0 + 0.5 + 2.0),
    (term(["lat.dispatch"], within="flush.icasc"), 0.5),
    (term(["lat.dispatch"], within="cascade", outside="flush"), 1.0 + 2.0),
    (term(["lat.dispatch", "topo.dispatch"], "count", within="cascade", outside="flush"), 2),
    (term(["flush.replay.*", "flush.icasc"]), 0.5 + 3.0),
    (term(["flush"], "self"), 4.0 - 0.5 - 3.0),
    (term(["wave.union"], "self", outside="flush"), (3.0 - 2.0) + (4.0 - 2.0)),
    (term(["no.such.span"]), 0.0),
])
def test_terms_on_a_hand_made_record(index, t, want):
    assert index.term(t) == pytest.approx(want)


def test_value_is_add_less_subtract_over_the_counter(index):
    args = {"add": [term(["flush.icasc"])],
            "subtract": [term(["topo.dispatch", "topo.readback"], within="flush.icasc")],
            "per": "rounds", "scale": 1000.0}
    assert program_span.compute(args, index, 2) == pytest.approx((3.0 - 1.5) / 2 * 1000.0)
    assert program_span.compute(args, index, 0) is None
    empty = program_span.SpanIndex(hand_made(), (100.0, 200.0))
    assert program_span.compute(args, empty, 2) is None
    with pytest.raises(ValueError):
        index.term(term(["cascade"], "median"))


def ctx_for(window=(10.0, 30.0), **counters):
    return SimpleNamespace(m=SimpleNamespace(window=window, counters=counters))


def test_read_takes_the_programs_record_through_its_accessor(monkeypatch):
    from stl_fusion_tpu.diagnostics import tracing

    args = {"add": [term(["cascade"], "count")], "per": "waves", "scale": 1.0}
    monkeypatch.setattr(tracing, "hot_spans", hand_made)
    assert program_span.read(args, ctx_for(waves=2)) == 1.0
    assert program_span.read(args, ctx_for(other=2)) is None  # no such counter
    # nothing recorded (an untraced run): no value
    monkeypatch.setattr(tracing, "hot_spans", list)
    assert program_span.read(args, ctx_for(waves=2)) is None
    # a record that filled up may have lost the window's start: no value
    monkeypatch.setattr(tracing, "hot_spans", hand_made)
    monkeypatch.setattr(tracing, "HOT_RECORD_CAP", len(hand_made()))
    assert program_span.read(args, ctx_for(waves=2)) is None
    # a program that has no such spans (the parent commit): no value, no raise
    monkeypatch.delattr(tracing, "hot_spans")
    assert program_span.read(args, ctx_for(waves=2)) is None


def span_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    out = {}
    for entry in manifest["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics", entry["name"] + ".json")) as f:
            if json.load(f)["reader"] == "program_span":
                (cell,) = entry["workloads"]
                out.setdefault(cell, []).append(entry["name"])
    return out


def rehearse(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    # a short window: on the rehearsal's toy graph the burst cell's churn
    # forces a mirror rebuild, and so a counted restage, at the ninth
    # super-round, which a fast host reaches in half a second
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "span_run.py"), "--workload", cell,
         "--seed", str(2**31 + 26), "--seconds", "0.25", "--trace", str(trace),
         "--cpu-rehearsal"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line, extra = (json.loads(x) for x in proc.stdout.strip().splitlines()[-2:])
    assert line["device"]["platform"] == "cpu" and line["correct"] is True
    return line, extra


def test_ten_metrics_read_the_spans():
    cells = span_metrics()
    assert sorted(cells) == ["plawdag-1c-burst", "plawdag-1c-lone"]
    assert len(cells["plawdag-1c-lone"]) == 6 and len(cells["plawdag-1c-burst"]) == 4


def test_traced_lone_rehearsal_splits_the_edit():
    line, extra = rehearse("plawdag-1c-lone", 1)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(span_metrics()["plawdag-1c-lone"]) <= set(got)
    parts = [got[f"lone_{p}_ms_per_wave"] for p in ("pre_dispatch", "dispatch", "readback", "apply")]
    assert all(p > 0 for p in parts)
    edits = extra["bench_spans"]["lone_wave"]
    mean_ms = edits["seconds"] / edits["n"] * 1e3
    assert sum(parts) == pytest.approx(mean_ms, rel=0.10)
    # the counts, where the dispatch and the readback happen
    assert got["lat_dispatches_per_wave"] == 1.0 and got["lat_readbacks_per_wave"] == 1.0
    # every edit's span lies inside the benchmark's span around the same call
    assert extra["program_spans"] >= 5 * edits["n"]  # the five spans every edit has
    inside = extra["trace"]["enclosed"]["fusion:cascade"]
    assert inside["n"] >= edits["n"] and inside["inside"] == inside["n"]


def test_traced_burst_rehearsal_splits_the_flush():
    line, extra = rehearse("plawdag-1c-burst", 1)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(span_metrics()["plawdag-1c-burst"]) <= set(got)
    rounds = extra["counters"]["rounds"]
    flush_ms = extra["bench_spans"]["flush"]["seconds"] / rounds * 1e3
    parts = [got[f"flush_{p}_ms_per_round"] for p in ("replay", "icasc_wait", "icasc_host")]
    assert sum(parts) == pytest.approx(flush_ms, rel=0.10)
    assert 0 < got["superround_apply_ms_per_round"] <= (
        extra["bench_spans"]["harvest"]["seconds"] / rounds * 1e3)
    for inner in ("fusion:flush", "fusion:superround.apply"):
        inside = extra["trace"]["enclosed"][inner]
        assert inside["n"] > 0 and inside["inside"] == inside["n"], inner


@pytest.mark.parametrize("cell", ["plawdag-1c-burst", "plawdag-1c-lone"])
def test_untraced_rehearsal_records_no_span(cell):
    line, extra = rehearse(cell, 0)
    assert not set(line["metrics"]) & {m for ms in span_metrics().values() for m in ms}
    assert "trace" not in extra and extra["program_spans"] == 0
