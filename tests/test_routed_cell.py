"""The routed mesh deployment (``plawdag-4c``, cell ``plawdag-4c-routed``) on
the CPU's emulated devices: the backend's routed path
(``enable_mesh_routing`` + ``cascade_rows_batch_routed``, 4 members on 4
devices), built by the benchmark's own deployment, against the benchmark's
plain reference (``benchmarks/lib/hostgraph.py``) on seeded graphs; each
planted fault of the mesh path seen by the driver's ``check``; and what a
rehearsal says when it has too few devices. Tiny sizes: no number here is a
device number.
"""
import asyncio
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
CELL = "plawdag-4c-routed"
NODES = 100_000  # 25 k rows a device: a whole-graph closure overflows the id buffers
CAP_PER_DEVICE = 16_384  # run_wave_collect's cap=65536 over 4 devices


def make_ctx(graph_seed: int):
    """The harness's own context for the cell, at rehearsal size, on the
    graph of ``graph_seed``."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    import run

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    with open(os.path.join(BENCH, "configs", cell["config"] + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)
    config["rehearsal"].update(nodes=NODES, graph_seed=graph_seed)
    args = run.parse_args(["--workload", CELL, "--cpu-rehearsal", "--seed", "7"])
    return run.Ctx(args, manifest, cell, config, traffic)


@pytest.fixture(scope="module", params=[0, 1, 2], ids=lambda s: f"graph{s}")
def dep(request):
    ctx = make_ctx(request.param)
    from deployments import routed_dag

    built = asyncio.run(routed_dag.build(ctx))
    yield built
    asyncio.run(routed_dag.close(built))


def seeds_for(dep, kind: str) -> np.ndarray:
    """Seed rows whose owners are one member, or all four; or a set from the
    lowest tenth of ids, whose closure is nearly the whole graph."""
    rng = np.random.default_rng(5)
    n = dep.n
    if kind == "overflow":
        return rng.choice(n // 10, size=200, replace=False)
    owner = dep.routed.perm[:n] // dep.routed.n_local
    upper = np.arange(n // 2, n)  # closures of tens of rows
    if kind == "one_member":
        return rng.choice(upper[owner[upper] == 0], size=40, replace=False)
    return np.concatenate([
        rng.choice(upper[owner[upper] == d], size=10, replace=False) for d in range(4)
    ])


@pytest.mark.parametrize("kind", ["one_member", "all_members", "overflow"])
def test_routed_wave_equals_the_reference(dep, kind):
    seeds = seeds_for(dep, kind)
    owners = set((dep.routed.perm[seeds] // dep.routed.n_local).tolist())
    if kind != "overflow":  # the lowest tenth of ids is six shards: any members
        assert owners == ({0} if kind == "one_member" else {0, 1, 2, 3})
    want = dep.oracle.closure(seeds)
    overflows = dep.metric("fusion_mesh_routed_overflows_total")
    levels = dep.routed.levels_total

    count = dep.backend.cascade_rows_batch_routed(dep.block, seeds)

    assert count == np.count_nonzero(want)
    assert np.array_equal(~np.asarray(dep.table.valid_mask), want)
    assert np.array_equal(dep.gdev.invalid_mask(), want)
    assert np.array_equal(dep.routed.invalid_mask(), want)
    assert dep.routed.levels_total > levels
    newly_by_device = np.bincount(
        dep.routed.perm[np.flatnonzero(want)] // dep.routed.n_local, minlength=4
    )
    overflowed = dep.metric("fusion_mesh_routed_overflows_total") - overflows
    assert overflowed == int(newly_by_device.max() > CAP_PER_DEVICE)
    assert overflowed == (kind == "overflow")
    # the closure crossed members, whatever member the seeds sat on
    assert np.count_nonzero(newly_by_device) == 4

    dep.restore()
    assert dep.table.stale_count() == 0
    assert not dep.gdev.invalid_mask().any()
    assert dep.layout_compared()["value"] == 0 and not dep.fallbacks_compared()[0]


def test_the_routed_compile_is_a_recorded_warm(dep):
    from stl_fusion_tpu.graph.program_cache import program_warm_report

    dep.backend.cascade_rows_batch_routed(dep.block, [dep.n - 1])
    dep.restore()
    warm = program_warm_report()["routed_collect"]
    assert warm["warm_s"] > 0 and "a2a" in warm["key"]


def run_harness(script, *args, env=None):
    return subprocess.run(
        [sys.executable, script, *args, "--workload", CELL, "--cpu-rehearsal",
         "--seconds", "0.5", "--seed", "11", "--trace", "0"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})),
        capture_output=True, text=True, timeout=300,
    )


@pytest.mark.parametrize("fault, seen_by", [
    ("one_device", {"layout_misplaced_arrays"}),
    ("dropped_cross_edges",
     {"wave_count_mismatches", "stale_mask_mismatches", "graph_mask_mismatches"}),
    ("one_level_early",
     {"wave_count_mismatches", "stale_mask_mismatches", "graph_mask_mismatches"}),
])
def test_fault_underneath_makes_the_run_incorrect(fault, seen_by):
    proc = run_harness(os.path.join(BENCH, "tests", "routed_fault_run.py"), fault)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, line["compared"]
    over = {name for name, (value, limit) in line["compared"].items() if value > limit}
    assert over == seen_by


def test_rehearsal_with_too_few_devices_says_what_it_needs():
    proc = run_harness(os.path.join(BENCH, "run.py"), env={"XLA_FLAGS": ""})
    assert proc.returncode == 2, proc.stderr[-3000:]
    assert "--xla_force_host_platform_device_count=4" in proc.stderr
    assert not proc.stdout.strip()  # no result line
