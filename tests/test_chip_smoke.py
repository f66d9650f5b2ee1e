"""chip_smoke.py must not rot between chip runs: its explicit CPU dry run is
driven here at a tiny size (subprocesses: the smoke owns its process), and
the two ways it must FAIL are checked: no TPU without the dry-run flag, and
a counted fallback firing while every oracle still agrees."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_smoke(*args, devices: int = 1, env_extra=None):
    env = dict(os.environ)
    # the smoke never builds a device pool; the CALLER decides how many
    # virtual CPU devices a dry run sees
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )


def record_and_verdict(proc) -> tuple[dict, dict]:
    """Stdout is two JSON lines: the record, then the driver's verdict."""
    lines = proc.stdout.strip().splitlines()
    assert len(lines) == 2, lines
    return json.loads(lines[0]), json.loads(lines[1])


def test_cpu_dry_run_meets_the_json_contract():
    proc = run_smoke("--cpu-dry-run", "--nodes", "20000", devices=4)
    assert proc.returncode == 0, proc.stderr[-3000:]
    d, verdict = record_and_verdict(proc)
    # the last line has exactly the keys the driver's contract names
    assert verdict == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu", "count": 4},
    }
    assert d["ok"] is True and d["problems"] == []
    assert d["device"] == verdict["device"]
    assert d["platform"] == "cpu" and d["device_kind"] == "cpu"
    # every leg ran and passed; the mesh leg saw the caller's 4 devices
    assert d["legs"] == {
        "serve": {"ok": True}, "kernel": {"ok": True}, "mesh": {"ok": True},
    }
    assert d["reduced"][0] == {"what": "nodes", "from": 10_000_000, "to": 20000}
    # the three serve oracles
    assert d["lone_waves"]["samples"] == 8 and d["lone_waves"]["diverged"] == 0
    assert d["superrounds"]["diverged"] == 0
    assert d["superrounds"]["churn_edges_declared"] > 0
    assert d["write"]["client_equals_store"] is True and d["write"]["journaled"]
    # every fallback counter is listed, and zero
    for name in (
        "watchdog_faults", "watchdog_fallbacks", "superround_eager_rounds",
        "superround_faults", "superround_restages",
        "superround_forced_harvests", "pipeline_eager_waves",
        "pipeline_chain_faults", "mesh_member_relays", "tree_fallbacks",
        "hier_fallbacks",
    ):
        assert d["fallbacks"][name] == 0, name
    # Pallas ran interpreted because the flag said so
    assert d["kernels"]["or_popcount"] == {
        "interpret": True, "words": 625, "matches_numpy": True,
    }
    assert d["mesh"]["static"]["oracle_exact"] is True
    assert d["mesh"]["static"]["shard_devices"] == [0, 1, 2, 3]
    assert d["mesh"]["live"]["oracle_divergence"] == 0
    assert d["mesh"]["live"]["reshard_moves"] > 0
    assert d["compile_cache"]["dir"] == os.path.join(REPO, ".jax_cache")
    assert d["compile_cache"]["entries_after"] >= d["compile_cache"]["entries_before"]
    assert d["dispatch_roundtrip_ms_median"] > 0
    assert d["native_graphpack"] in ("loaded", "numpy path served")
    for name in ("union", "refresh", "superround", "wave_chain"):
        assert d["program_warms"][name]["warm_s"] >= 0
    for key in ("graph_build", "mirror_build"):
        assert d["seconds"][key] >= 0


def test_no_tpu_without_the_dry_run_flag_fails_before_building():
    proc = run_smoke()
    assert proc.returncode == 2
    assert proc.stdout.strip() == "", "a run without a TPU must print no result"
    assert "no TPU" in proc.stderr


def test_counted_fallback_fails_the_smoke_though_every_oracle_agrees():
    """The watchdog's chaos hook makes the next fused dispatch raise: the
    host loop answers right (that is the failure model working), and the
    smoke must still exit nonzero, because a fallback that hides the device
    is exactly what it exists to catch."""
    proc = run_smoke(
        "--cpu-dry-run", "--nodes", "20000", "--legs", "serve", "--inject-fault"
    )
    assert proc.returncode == 1, proc.stderr[-3000:]
    d, verdict = record_and_verdict(proc)
    assert verdict == {"ok": False, "device": d["device"]}
    assert d["ok"] is False
    assert d["fallbacks"]["watchdog_faults"] == 1
    assert d["fallbacks"]["watchdog_fallbacks"] >= 1
    assert d["lone_waves"]["diverged"] == 0  # the host loop was right
    assert any("fallback counters nonzero" in p for p in d["problems"])
    assert d["legs"]["kernel"] == "not run: not selected"


def test_chip_run_refuses_a_cut_below_the_floor():
    proc = run_smoke("--nodes", "20000")
    assert proc.returncode == 2 and "--cpu-dry-run" in proc.stderr
    assert proc.stdout.strip() == ""
