"""The two readers the routed cell brought: ``trace_op_time`` on a hand-made
trace of two chips whose answers are known, and ``roofline_mesh`` on shapes
counted by hand.
"""
import json
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

from lib.trace import TraceSummary  # noqa: E402
from readers import roofline_mesh, trace_op_time  # noqa: E402

with open(os.path.join(BENCH, "layer_metrics", "routed_collective_s_per_wave.json")) as f:
    COLLECTIVES = json.load(f)["args"]["ops"]  # the expression the cell's metric uses


def two_chips():
    """One execution of ``jit_collect`` on each of two chips: a level loop
    (``while``) around an all-to-all, a fusion and an all-reduce, and one
    run of another program with an all-reduce of its own."""
    def plane(i, a2a, a2a_ns, reduce_start, reduce_done):
        return {"name": f"/device:TPU:{i}", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_collect(7)", 0.0, 100.0), ("jit_prog(3)", 120.0, 20.0)]},
            {"name": "XLA Ops", "events": [
                ("while.1", 0.0, 100.0),
                (a2a, 10.0, a2a_ns), ("fusion.9", 50.0, 30.0),
                (reduce_start, 90.0, 4.0), (reduce_done, 94.0, 2.0),
                ("all-reduce.8", 120.0, 20.0)]},
        ]}

    host = {"name": "/host:CPU", "lines": [{"name": "main", "events": [
        ("bench:window", 0.0, 200.0)]}]}
    # one chip names its instructions by HLO opcode, the other, as the v5e's
    # trace does, after the JAX primitive
    return [plane(0, "all-to-all.4", 10.0, "all-reduce-start.2", "all-reduce-done.2"),
            plane(1, "all_to_all.4", 30.0, "psum.2", "psum.3"), host]


def ctx_for(trace, counters, chips=2, peaks=None, edges=0):
    m = types.SimpleNamespace(trace=trace, counters=counters, values={"edges": edges})
    sizes = {"nodes": 1000, "row_words": 1}
    return types.SimpleNamespace(
        m=m, peaks=peaks or {}, cell={"chips": chips}, size=sizes.__getitem__,
    )


def test_op_seconds_sums_the_families_of_one_program():
    t = TraceSummary(two_chips())
    assert t.devices_busy == 2
    # chip 0: 10 + 4 + 2, chip 1: 30 + 4 + 2; jit_prog's all-reduce is not counted
    assert trace_op_time.op_seconds(t.device_ops, "^jit_collect$", COLLECTIVES) \
        == pytest.approx(52e-9)
    assert trace_op_time.op_seconds(t.device_ops, "^jit_prog$", COLLECTIVES) \
        == pytest.approx(40e-9)
    assert trace_op_time.op_seconds(t.device_ops, "^jit_collect$", "^fusion$") \
        == pytest.approx(60e-9)
    # a loop's own event keeps only what its body left
    assert dict(t.device_ops)["jit_collect:while"] == pytest.approx((54 + 34) * 1e-9)


def test_trace_op_time_is_a_mean_over_the_chips_per_counter():
    t = TraceSummary(two_chips())
    args = {"program": "^jit_collect$", "ops": COLLECTIVES, "per": "waves"}
    assert trace_op_time.read(args, ctx_for(t, {"waves": 1})) == pytest.approx(26e-9)
    assert trace_op_time.read(dict(args, scale=1e9), ctx_for(t, {"waves": 2})) \
        == pytest.approx(13.0)


def test_trace_op_time_has_no_value_where_there_is_nothing_to_read():
    t = TraceSummary(two_chips())
    args = {"program": "^jit_collect$", "ops": COLLECTIVES, "per": "waves"}
    assert trace_op_time.read(args, ctx_for(None, {"waves": 1})) is None  # untraced
    assert trace_op_time.read(args, ctx_for(t, {})) is None  # no such counter
    assert trace_op_time.read(dict(args, program="^jit_absent$"),
                              ctx_for(t, {"waves": 1})) is None
    assert trace_op_time.read(dict(args, ops="^all-gather$"),
                              ctx_for(t, {"waves": 1})) is None
    assert trace_op_time.op_seconds(t.device_ops, "^jit_collect$", "^psum$") \
        == pytest.approx(6e-9)


BYTES = [{"count": "edges", "row_words_times": 4, "plus": 4},
         {"count": "nodes", "row_words_times": 8, "plus": 0}]


def test_least_seconds_counts_every_chips_bandwidth():
    sizes = {"nodes": 1000, "edges": 3000, "row_words": 1}
    # 3000 x (4 + 4) + 1000 x 8 bytes
    assert roofline_mesh.least_seconds(BYTES, sizes, 1, 32000.0) == pytest.approx(1.0)
    assert roofline_mesh.least_seconds(BYTES, sizes, 4, 32000.0) == pytest.approx(0.25)


def test_roofline_mesh_reads_the_programs_mean_time():
    t = TraceSummary(two_chips())  # jit_collect: 100 ns on each chip, one run
    args = {"bytes": BYTES, "program": "^jit_collect$", "per": "waves"}
    peaks = {"hbm_bytes_per_s": 32000.0 / 50e-9}  # one chip: 50 ns; two: 25 ns
    ctx = ctx_for(t, {"waves": 1}, chips=2, peaks=peaks, edges=3000)
    assert roofline_mesh.read(args, ctx) == pytest.approx(25.0)
    assert roofline_mesh.read(args, ctx_for(t, {"waves": 1}, edges=3000)) is None  # no peaks
    assert roofline_mesh.read(dict(args, program="^jit_absent$"), ctx) is None
