"""The harness sees a broken timed path: for each fault a cell can have, a
whole rehearsal run with the fault planted underneath reports ``correct``
false; and the controls (the reference under one broken guarantee) come out
incorrect while the sound run is correct. CPU, tiny sizes: no number here is
a device number.
"""
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CELL = {
    "burst": "plawdag-1c-burst",
    "lone": "plawdag-1c-lone",
}


def run_line(argv, timeout=300):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable] + argv, capture_output=True, text=True, env=env,
        timeout=timeout,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def rehearse(cell, *extra, fault=None):
    args = ["--workload", CELL[cell], "--seed", "11", "--seconds", "0.5",
            "--cpu-rehearsal", *extra]
    if fault is None:
        return run_line([os.path.join(BENCH, "run.py")] + args)
    return run_line([os.path.join(HERE, "fault_run.py"), fault] + args)


@pytest.mark.parametrize("cell", sorted(CELL))
def test_sound_run_is_correct_and_its_control_is_not(cell):
    line, err = rehearse(cell, "--trace", "0", "--control", "1")
    assert line["device"]["platform"] == "cpu"
    assert line["correct"] is True, line["compared"]
    assert all(v <= lim for v, lim in line["compared"].values())
    assert list(line)[-1] == "compared"  # the compared numbers come last
    assert "compared " in err and err.rstrip().splitlines()[-1].startswith("correct:")
    assert line["control"], "the cell has no control"
    for kind, got in line["control"].items():
        assert got["correct"] is False, (kind, got)


@pytest.mark.parametrize("fault", [
    "burst_answer_altered", "burst_half_batch", "burst_state_unchanged",
    "lone_answer_altered", "lone_state_unchanged",
])
def test_fault_underneath_makes_the_run_incorrect(fault):
    line, _err = rehearse(fault.split("_", 1)[0], "--trace", "0", fault=fault)
    assert line["correct"] is False, line["compared"]
    assert any(v > lim for v, lim in line["compared"].values())
