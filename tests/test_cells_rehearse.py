"""The measured path from its entry point: every cell of ``BENCHMARK.json``
rehearsed through ``benchmarks/run.py --cpu-rehearsal`` (a subprocess: the
harness owns its process), traced and untraced. A package change that breaks
a cell's driver, or renames a span or counter a per-layer metric reads, fails
here and not first on the chip. CPU, tiny sizes: no number here is a device
number; only that the run is ``correct`` and that the metrics have values.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
with open(os.path.join(REPO, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
# readers that take a metric from the program's own spans and counters; the
# others need the profiler's device plane or the device's memory statistics,
# which a CPU run lacks
PROGRAM_READERS = ("program_span", "counter_delta")


def program_metrics(cell: str) -> list:
    """The per-layer metrics declared for ``cell`` that a CPU trace can fill."""
    names = []
    for metric in BENCHMARK["per_layer"]:
        if cell not in metric.get("workloads", CELLS):
            continue
        with open(os.path.join(BENCH, "layer_metrics", metric["name"] + ".json")) as f:
            if json.load(f)["reader"] in PROGRAM_READERS:
                names.append(metric["name"])
    return names


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses_correct(cell, trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--cpu-rehearsal", "--seconds", "0.5", "--seed", "11",
         "--trace", str(trace)],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["workload"] == cell and line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line["compared"]
    assert line["compared"], "the cell compared nothing"
    for name, (value, limit) in line["compared"].items():
        assert value <= limit, (name, value, limit)
    assert line["attempted"] > 0 and line["failed"] == 0
    if trace:
        wanted = program_metrics(cell)
        assert wanted, "no per-layer metric of this cell reads the program"
        for name in wanted:
            assert line["metrics"].get(name, {}).get("value") is not None, name
