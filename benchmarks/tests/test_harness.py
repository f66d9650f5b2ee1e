"""The harness's own parts: the manifest keeps to the contract's form, the
peaks table refuses an unknown chip, a run without a TPU fails with no
result, the generator is the program's, and the traced rehearsal reports the
per-layer metrics that need no device. CPU only.
"""
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_manifest_form(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks"]
    assert 1 <= manifest["run_seconds"] <= 51
    cells = {w["name"]: w for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {e["name"]: e for e in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["source"]) <= 200 and len(c["why"]) <= 200
        assert c["file"].startswith("benchmarks/") and os.path.exists(os.path.join(ROOT, c["file"]))
        assert any(w["config"] == c["name"] for w in manifest["workloads"])
    pairs = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert w["config"] in configs and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    for e in manifest["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert NAME.match(e["name"]) and UNIT.match(e["unit"])
        assert e["better"] in ("lower", "higher") and 0.01 <= e["bound"] <= 0.25
        assert e["source"] in ("host_clock", "device_trace")
        assert all(w in cells for w in e.get("workloads", []))
    names = [e["name"] for e in manifest["end_to_end"] + manifest["per_layer"]]
    assert len(names) == len(set(names))
    for p in manifest["per_layer"]:
        assert set(p) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert NAME.match(p["name"]) and UNIT.match(p["unit"])
        assert p["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        assert p["moves"] in e2e
        spec_path = os.path.join(BENCH, "layer_metrics", p["name"] + ".json")
        with open(spec_path) as f:
            spec = json.load(f)
        assert spec["layer"] == p["layer"] and spec["moves"] == p["moves"]
        assert os.path.exists(os.path.join(BENCH, "readers", spec["reader"] + ".py"))
        moved_in = e2e[p["moves"]].get("workloads", list(cells))
        assert all(w in moved_in for w in p.get("workloads", []))
        if p["name"].endswith("_roofline"):
            assert p["unit"] == "%"


def test_every_cell_reports_an_end_to_end_and_a_layer_metric(manifest):
    for w in manifest["workloads"]:
        own = [e for e in manifest["end_to_end"]
               if e["name"] != "setup_s" and w["name"] in e.get("workloads", [w["name"]])]
        assert own, w["name"]
        assert any(w["name"] in p.get("workloads", [w["name"]]) for p in manifest["per_layer"])


def test_unknown_device_kind_is_an_error():
    from lib.device import peaks_for

    assert peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks_for("TPU v9 imaginary")


def test_generator_is_the_programs():
    from lib.hostgraph import power_law_dag
    from stl_fusion_tpu.graph.synthetic import power_law_dag as theirs

    for n, seed in ((5000, 0), (20000, 2**31 + 17)):
        a, b = power_law_dag(n, seed=seed), theirs(n, avg_degree=3.0, seed=seed)
        assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])


def test_hostgraph_closures_agree():
    from lib.hostgraph import HostGraph, power_law_dag

    src, dst = power_law_dag(3000, seed=5)
    g = HostGraph(src, dst, 3000)
    for seed in (7, 1500, 2900):
        assert set(np.flatnonzero(g.closure([seed])).tolist()) == g.closure_ids([seed])
    before = g.closure_ids([10])
    new = max(set(range(3000)) - before)
    g.add_edges(np.array([10]), np.array([new]))
    assert new in g.closure_ids([10])
    assert g.closure([10])[new] and not g.closure([10], chunks=1)[new]
    assert g.closure_ids([10], chunks=1) == before
    assert g.closure_ids([10], max_depth=1) <= g.closure_ids([10])


def test_own_levels_are_the_mirrors():
    """The churn is oriented by the benchmark's own level table; on this
    configuration's graph it is the table the program's topo mirror holds,
    so every declared edge patches the mirror in place."""
    from lib.hostgraph import HostGraph, power_law_dag
    from stl_fusion_tpu.ops.topo_wave import build_topo_graph

    n = 20000
    src, dst = power_law_dag(n, seed=0)
    own = HostGraph(src, dst, n).levels()
    topo = build_topo_graph(src, dst, n, k=4)
    pos = topo.inv_perm[np.arange(n)]
    theirs = np.searchsorted(np.asarray(topo.level_starts), pos, side="right") - 1
    assert np.array_equal(own, theirs)
    for u, v in zip(src[:2000].tolist(), dst[:2000].tolist()):
        assert own[u] < own[v]


def _run(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, os.path.join(BENCH, "run.py")] + args,
                          capture_output=True, text=True, env=env, timeout=300)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    proc = _run(["--workload", "plawdag-1c-lone", "--seed", "1", "--seconds", "1",
                 "--trace", "0"])
    assert proc.returncode == 2
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("cell,expects", [
    ("plawdag-1c-burst", {"mirror_patch_ms_per_round"}),
    ("plawdag-1c-lone", {"lat_served_share"}),
])
def test_traced_rehearsal_reports_layer_metrics(cell, expects):
    proc = _run(["--workload", cell, "--seed", str(2**31 + 5), "--seconds", "0.5",
                 "--trace", "1", "--cpu-rehearsal"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu" and line["correct"] is True
    got = set(line["metrics"])
    assert expects | {"program_warm_s", "graph_build_s"} <= got
    # a share of a roofline or a time read from the device's trace is never
    # made up without a device
    assert not any(k.endswith("_roofline") or "device" in k for k in got)
    assert "host_prep_ms_per_round" not in got
