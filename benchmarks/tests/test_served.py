"""The served cell (``plawdag-served-1c-writes``) on the CPU: the rehearsal
is correct and reports the per-layer metrics that need no device, both
controls come out incorrect, each planted fault of the write path is seen,
and the plain reference keeps to its rules. Tiny sizes: no number here is a
device number.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]
CELL = "plawdag-served-1c-writes"


def run_line(argv, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable] + argv, capture_output=True, text=True, env=env,
        timeout=timeout,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def rehearse(*extra, fault=None, seed=11):
    args = ["--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
            "--cpu-rehearsal", *extra]
    if fault is None:
        return run_line([os.path.join(BENCH, "run.py")] + args)
    return run_line([os.path.join(HERE, "served_fault_run.py"), fault] + args)


def test_sound_run_is_correct_and_both_controls_are_not():
    line = rehearse("--trace", "0", "--control", "1", seed=2**31 + 9)
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"lone_wave_p50_ms", "lone_wave_p95_ms", "setup_s"}
    counters = line["notes"]["counters"]
    assert counters["commands"] >= 5
    assert counters["lat_waves"] == counters["commands"]
    assert counters["fused_dispatches"] == 0 and not line["notes"]["fallbacks"]
    assert set(line["control"]) == {"direct_only", "lost_write"}
    for kind, got in line["control"].items():
        assert got["correct"] is False, (kind, got)
    lost = line["control"]["lost_write"]["compared"]
    assert lost["journal_mismatches"][0] == 1 and lost["store_mismatches"][0] == 1


def test_traced_rehearsal_reports_the_write_paths_layers():
    line = rehearse("--trace", "1")
    assert line["correct"] is True, line["compared"]
    got = set(line["metrics"])
    assert {
        "cmd_call_ms_per_cmd", "cmd_execute_ms_per_cmd", "cmd_journal_ms_per_cmd",
        "cmd_submit_ms_per_cmd", "cmd_wave_ms_per_cmd", "cmd_lat_served_share",
        "fanout_ms_per_cmd", "fanout_keys_per_frame", "outbox_wait_ms_per_cmd",
        "reread_ms_per_cmd", "tick_wait_ms_per_cmd", "deliver_ms_per_cmd",
        "program_warm_s", "graph_build_s",
    } <= got
    assert "cmd_wave_device_ms_per_cmd" not in got  # no device, no device time
    assert not any(k.startswith(("lone_", "lat_")) for k in got)  # the lone cell's
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["cmd_lat_served_share"] == 100.0
    # the parts of a command add up to its sample
    parts = (m["cmd_call_ms_per_cmd"] + m["cmd_execute_ms_per_cmd"]
             + m["cmd_journal_ms_per_cmd"] + m["cmd_submit_ms_per_cmd"]
             + m["tick_wait_ms_per_cmd"] + m["cmd_wave_ms_per_cmd"]
             + m["fanout_ms_per_cmd"] + m["deliver_ms_per_cmd"])
    assert abs(parts - line["notes"]["ms_mean"]) <= 0.1 * line["notes"]["ms_mean"]


@pytest.mark.parametrize("fault", [
    "subscription_never_fires", "unjournaled_ack", "lost_write", "doubled_write",
])
def test_fault_underneath_makes_the_run_incorrect(fault):
    line = rehearse("--trace", "0", fault=fault)
    assert line["correct"] is False, line["compared"]
    assert any(v > lim for v, lim in line["compared"].values())


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "lib", "servedref.py")) as f:
        source = f.read()
    assert "import stl_fusion_tpu" not in source and "from stl_fusion_tpu" not in source


def test_reference_rules():
    """A chain 0 -> 1 -> 2 -> 3 and a side edge 1 -> 4, by hand."""
    from lib.hostgraph import HostGraph
    from lib.servedref import replay

    g = HostGraph(np.array([0, 1, 1, 2]), np.array([1, 2, 4, 3]), 5)
    subs = [("a", 0), ("b", 1), ("c", 3)]
    events = [
        ("cmd", "op0", 0, 2.0),
        ("reread", "a", 0, None), ("reread", "b", 1, None),
        ("cmd", "op1", 0, 3.0),
    ]
    out = replay(g, subs, events)
    assert out.journal == ["op0", "op1"] and out.store[0] == np.float32(5.0)
    # the first wave takes the whole closure; the second finds 2 still
    # invalid, so 1 is newly invalid again and 3 stays behind its parent
    assert out.newly_counts == [5, 2]
    assert out.observers == [frozenset(subs), frozenset({("a", 0), ("b", 1)})]
    assert out.reread_values == [np.float32(2.0), np.float32(1.0)]
    assert out.table_stale == {0, 1, 2, 3, 4}  # a re-read leaves the table's row stale
    assert replay(g, subs, events, max_depth=1).newly_counts == [2, 2]
    dropped = replay(g, subs, events, drop_op="op1")
    assert dropped.journal == ["op0"] and dropped.store[0] == np.float32(2.0)
