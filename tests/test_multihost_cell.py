"""The multi-host deployment (``plawdag-mh-4c``, cell ``plawdag-mh-4c-writes``)
on the CPU's emulated devices: the plain reference (``benchmarks/lib/mhref.py``)
against hand-made cases; the system against it on seeded traffic, with both
controls incorrect; a backend given a device keeping everything there, two of
them in one process running interleaved waves with exact counts and no
device-to-device transfer; a replica's reader taking the pipeline's road; and
each planted fault seen by the driver's ``check``. Tiny sizes: no number here
is a device number.
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
CELL = "plawdag-mh-4c-writes"
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


# --------------------------------------------------------------- the reference
def chain_graph():
    """0 -> 1 -> 2 -> 3, and 0 -> 4."""
    from lib.hostgraph import HostGraph

    return HostGraph(np.array([0, 1, 2, 0]), np.array([1, 2, 3, 4]), 5)


MEMBERS = ["m0", "m1", "m2", "m3"]


def test_mhref_owner_is_the_programs_shard_map():
    """The reference computes the shard map itself; it has to name the
    member the program's ``ShardMap`` names, for every row."""
    from lib import mhref
    from stl_fusion_tpu.cluster import ShardMap

    smap = ShardMap.initial(MEMBERS, n_shards=256)
    for row in range(0, 4000, 7):
        assert mhref.owner_of_row(row, MEMBERS, 256) == smap.owner_of(repr(f"row-{row}"))
    owners = {mhref.owner_of_row(row, MEMBERS, 256) for row in range(400)}
    assert owners == set(MEMBERS)


def test_mhref_every_member_sees_every_command():
    from lib import mhref

    subs = {m: [(0, 0), (1, 1)] for m in MEMBERS}
    subs["m3"] = [(0, 3)]  # m3 watches the end of the chain only
    owner = mhref.owner_of_row(0, MEMBERS, 256)
    events = [
        ("cmd", "a", 0, 2.0, owner),
        ("reread", "m0", 0, 0, 2.0),  # m0's client re-reads row 0: valid again THERE
        ("cmd", "b", 0, 3.0, owner),
    ]
    got = mhref.replay(chain_graph(), MEMBERS, 256, subs, events)
    assert got.journal == [("a", owner), ("b", owner)]
    assert got.store == {0: np.float32(5.0)}
    # first wave: the whole graph, on every member
    assert [got.members[m].newly_counts[0] for m in MEMBERS] == [5, 5, 5, 5]
    assert got.members["m1"].observers[0] == frozenset({(0, 0), (1, 1)})
    assert got.members["m3"].observers[0] == frozenset({(0, 3)})
    # second wave: only m0 re-read row 0, so only there does it count again
    assert [got.members[m].newly_counts[1] for m in MEMBERS] == [1, 0, 0, 0]
    assert got.members["m0"].observers[1] == frozenset({(0, 0)})
    assert got.members["m1"].observers[1] == frozenset()
    assert got.members["m0"].reread_values == [np.float32(2.0)]
    assert all(got.members[m].table_stale == {0, 1, 2, 3, 4} for m in MEMBERS)
    # every member replays what it does not own
    assert got.replays == {m: (0 if m == owner else 2) for m in MEMBERS}


def test_mhref_controls_differ_from_the_reference():
    from lib import mhref

    subs = {m: [(0, 3)] for m in MEMBERS}
    owner = mhref.owner_of_row(0, MEMBERS, 256)
    events = [("cmd", "a", 0, 1.0, owner)]
    whole = mhref.replay(chain_graph(), MEMBERS, 256, subs, events)
    direct = mhref.replay(chain_graph(), MEMBERS, 256, subs, events, max_depth=1)
    assert whole.members["m1"].newly_counts == [5]
    assert direct.members["m1"].newly_counts == [3]  # 0, 1 and 4
    assert direct.members["m1"].observers == [frozenset()]
    other = next(m for m in MEMBERS if m != owner)
    lost = mhref.replay(chain_graph(), MEMBERS, 256, subs, events, lost_replay=(other, "a"))
    assert lost.members[other].newly_counts == [0]
    assert lost.members[other].observers == [frozenset()]
    assert lost.members[other].table_stale == set()
    assert lost.replays[other] == 0
    assert lost.members[owner].newly_counts == [5] and lost.store == whole.store
    with pytest.raises(ValueError):
        mhref.replay(chain_graph(), MEMBERS, 256, subs, [("bogus",)])


# ------------------------------------------------------------ backends on chips
def build_backend(device, n, src, dst):
    from deployments import table_dag
    from stl_fusion_tpu.core import FusionHub, memo_table_of
    from stl_fusion_tpu.graph import TpuGraphBackend

    hub = FusionHub()
    backend = TpuGraphBackend(
        hub, node_capacity=n + 64, edge_capacity=len(src) + 50_000, device=device
    )
    svc = table_dag.make_service(n)(hub)
    hub.add_service(svc, "dag")
    table = memo_table_of(svc.node)
    block = backend.bind_table_rows(table)
    backend.declare_row_edges(block, src, block, dst)
    backend.warm_block_on_device(block)
    backend.flush()
    backend.graph.build_topo_mirror()
    pipe = hub.enable_nonblocking(fuse_depth=8, max_words=16)
    sr = backend.enable_super_rounds(block, depth=2, max_words=16)
    return {"hub": hub, "backend": backend, "table": table, "block": block,
            "pipe": pipe, "sr": sr, "device": device}


def misplaced(built) -> dict:
    want = [built["device"].id]
    return {
        name: where for name, where in built["backend"].device_layout().items()
        if where["devices"] != want or not where["committed"]
    }


def test_two_backends_on_two_devices_run_interleaved_waves():
    """Each backend's arrays committed to its own device and to it alone;
    waves, pipeline waves, a super-round and a refresh on both, interleaved,
    with exact counts, and not one device-to-device transfer (a staged
    argument that went by way of the default device would be one)."""
    import jax

    from lib.hostgraph import HostGraph, power_law_dag

    n = 20_000
    src, dst = power_law_dag(n, avg_degree=3.0, seed=3, alpha=0.8)
    oracle = HostGraph(src, dst, n)
    devices = jax.devices()
    assert len(devices) >= 3
    rng = np.random.default_rng(1)
    with jax.transfer_guard_device_to_device("disallow_explicit"):
        pair = [build_backend(devices[1], n, src, dst), build_backend(devices[2], n, src, dst)]
        try:
            invalid = [set(), set()]
            for step in range(12):
                for k, built in enumerate(pair):
                    backend, block = built["backend"], built["block"]
                    row = int(rng.integers(n // 2, n))
                    want = oracle.closure_ids([row]) - invalid[k]
                    if step % 2:
                        ticket = built["pipe"].submit_rows(block, [row])
                        built["pipe"].drain()
                        got = ticket.count
                    else:
                        got = backend.cascade_rows_batch(block, [row])
                    assert got == len(want), (step, k, row)
                    invalid[k] |= want
                if step == 5:
                    for k, built in enumerate(pair):
                        stale = np.flatnonzero(~np.asarray(built["table"].valid_mask))
                        assert set(stale.tolist()) == invalid[k]
                        built["backend"].refresh_block_on_device(built["block"])
                        built["backend"].flush()
                        assert built["table"].stale_count() == 0
                        invalid[k] = set()
            for built in pair:
                groups = [[int(r)] for r in rng.integers(n // 2, n, size=4)]
                want = [len(oracle.closure_ids(g)) for g in groups]
                staged = built["sr"].stage([groups] * 2)
                built["backend"].flush()
                built["backend"].refresh_block_on_device(built["block"])
                per_round = built["sr"].dispatch(staged).harvest()
                assert [int(c) for c in per_round[0]] == want
            for built in pair:
                assert misplaced(built) == {}
                assert len(built["backend"].device_layout()) >= 12
        finally:
            for built in pair:
                built["pipe"].dispose()
                built["sr"].dispose()
    on_default = [a for a in jax.live_arrays() if devices[0] in a.devices() and a.size >= n]
    assert not on_default, [(a.shape, a.dtype) for a in on_default]


def test_a_backend_without_a_device_names_none():
    """``device=None`` is the one-chip behaviour: no method is rebound, no
    array is committed anywhere."""
    from lib.hostgraph import power_law_dag

    n = 2_000
    src, dst = power_law_dag(n, avg_degree=3.0, seed=0, alpha=0.8)
    built = build_backend(None, n, src, dst)
    try:
        backend = built["backend"]
        assert backend.device is None and backend.graph.device is None
        assert "run_waves_union" not in vars(backend.graph)
        assert "refresh_block_on_device" not in vars(backend)
        assert built["table"]._put is built["table"]._jnp.asarray
        backend.cascade_rows_batch(built["block"], [n - 1])
        assert not any(w["committed"] for w in backend.device_layout().values())
    finally:
        built["pipe"].dispose()
        built["sr"].dispose()


# ------------------------------------------------------------ the reader's road
async def _two_hosts(pipeline: bool):
    """Host A (plain) and host B (a device backend on device 1, with or
    without a nonblocking pipeline), one log; B holds computeds for eight
    keys and an aggregate over them."""
    import jax

    from stl_fusion_tpu.commands import command_handler
    from stl_fusion_tpu.core import (
        ComputeService, FusionHub, capture, compute_method, is_invalidating,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.oplog import (
        InMemoryOperationLog, LocalChangeNotifier, attach_operation_log,
    )
    from stl_fusion_tpu.utils.serialization import wire_type
    import dataclasses

    db: dict = {}

    @wire_type("MhTestSet")
    @dataclasses.dataclass(frozen=True)
    class SetValue:
        key: str
        value: int

    class Values(ComputeService):
        @compute_method
        async def get(self, key: str) -> int:
            return db.get(key, 0)

        @command_handler
        async def set_value(self, command: SetValue):
            if is_invalidating():
                await self.get(command.key)
                return
            db[command.key] = command.value

    log_store, notifier = InMemoryOperationLog(), LocalChangeNotifier()
    hub_a = FusionHub()
    svc_a = Values(hub_a)
    hub_a.commander.add_service(svc_a)
    reader_a = attach_operation_log(hub_a.commander, log_store, notifier, start_reader=False)
    hub_b = FusionHub()
    backend = TpuGraphBackend(hub_b, device=jax.devices()[1])
    svc_b = Values(hub_b)
    hub_b.commander.add_service(svc_b)
    reader_b = attach_operation_log(hub_b.commander, log_store, notifier, start_reader=False)
    if pipeline:
        hub_b.enable_nonblocking(fuse_depth=8)
    keys = [f"k{i}" for i in range(8)]

    class Agg(ComputeService):
        @compute_method
        async def total(self) -> int:
            return sum([await svc_b.get(k) for k in keys])

    agg = Agg(hub_b)
    total = await capture(lambda: agg.total())
    nodes = {k: await capture(lambda k=k: svc_b.get(k)) for k in keys}
    return {"hub_a": hub_a, "SetValue": SetValue, "reader_a": reader_a,
            "reader_b": reader_b, "backend": backend, "total": total,
            "nodes": nodes, "keys": keys, "agg": agg}


async def test_reader_on_a_hub_with_a_pipeline_submits():
    """With a pipeline the batch's groups are submitted, one wave an
    operation, applied at the drain; no lane burst runs."""
    h = await _two_hosts(pipeline=True)
    backend, reader, pipe = h["backend"], h["reader_b"], h["backend"].pipeline
    try:
        for i, k in enumerate(h["keys"][:3]):
            await h["hub_a"].commander.call(h["SetValue"](k, 10 + i))
        assert await reader.read_new() == 3
        assert reader.replay_submitted == 3 and reader.replay_lane_bursts == 0
        assert pipe.stats()["pending_waves"] == 3
        assert not h["total"].is_invalidated  # not before the member drains
        newly = pipe.drain()
        assert newly >= 4  # three keys and the aggregate
        assert h["total"].is_invalidated
        assert pipe.stats()["pending_waves"] == 0 and pipe.waves_submitted == 3
        assert await h["agg"].total() == 10 + 11 + 12
        assert reader._collect_metrics()["fusion_oplog_replay_submitted_total"] == 3
        assert reader._collect_metrics()["fusion_oplog_replay_lane_bursts_total"] == 0
    finally:
        pipe.dispose()
        await h["reader_a"].stop()
        await reader.stop()


async def test_reader_without_a_pipeline_keeps_the_lane_burst():
    h = await _two_hosts(pipeline=False)
    reader = h["reader_b"]
    try:
        for i, k in enumerate(h["keys"][:3]):
            await h["hub_a"].commander.call(h["SetValue"](k, 20 + i))
        assert await reader.read_new() == 3
        assert reader.replay_lane_bursts == 1 and reader.replay_submitted == 0
        assert h["total"].is_invalidated  # applied before read_new returned
    finally:
        await h["reader_a"].stop()
        await reader.stop()


async def test_reader_stopped_mid_batch_still_hands_on_what_it_collected():
    """A reader cancelled between two records of a batch has advanced its
    watermark past the first: that record's invalidation is submitted all
    the same, and the next drain applies it."""
    from stl_fusion_tpu.operations.pipeline import OperationsHost

    h = await _two_hosts(pipeline=True)
    reader, pipe = h["reader_b"], h["backend"].pipeline
    notify, seen = OperationsHost.notify_completed, {"n": 0}

    async def cancelled_on_second(self, operation, is_local=True):
        if not is_local:
            seen["n"] += 1
            if seen["n"] == 2:
                raise asyncio.CancelledError()
        return await notify(self, operation, is_local)

    OperationsHost.notify_completed = cancelled_on_second
    try:
        for i, k in enumerate(h["keys"][:3]):
            await h["hub_a"].commander.call(h["SetValue"](k, 30 + i))
        with pytest.raises(asyncio.CancelledError):
            await reader.read_new()
        assert reader.replay_submitted == 1 and reader.watermark >= 2
        pipe.drain()
        first = h["nodes"]["k0"]
        assert first.is_invalidated or h["backend"]._pending[h["backend"].id_for(first)]
        assert h["total"].is_invalidated
    finally:
        OperationsHost.notify_completed = notify
        pipe.dispose()
        await h["reader_a"].stop()
        await reader.stop()


async def test_write_path_spans_are_recorded():
    """``oplog.read``, ``oplog.lag``, ``oplog.replay`` and ``oplog.batch``
    on the reader; off, the sites record nothing."""
    from stl_fusion_tpu.diagnostics import tracing

    h = await _two_hosts(pipeline=True)
    reader, pipe = h["reader_b"], h["backend"].pipeline
    try:
        await h["hub_a"].commander.call(h["SetValue"]("k0", 1))
        await reader.read_new()
        assert not tracing.hot_spans()
        tracing.enable_hot_spans()
        await h["hub_a"].commander.call(h["SetValue"]("k1", 2))
        await reader.read_new()
        by_name: dict = {}
        for r in tracing.hot_spans():
            by_name.setdefault(r.name, []).append(r)
        assert {"oplog.read", "oplog.lag", "oplog.replay", "oplog.batch"} <= set(by_name)
        assert len(by_name["oplog.lag"]) == 1 and len(by_name["oplog.replay"]) == 1
        lag = by_name["oplog.lag"][0]
        assert 0 <= lag.end - lag.start < 5.0
    finally:
        tracing.disable_hot_spans()
        pipe.dispose()
        await h["reader_a"].stop()
        await reader.stop()


# ------------------------------------------------- the system against mhref
def run_harness(script, *args, seed="23"):
    return subprocess.run(
        [sys.executable, script, *args, "--workload", CELL, "--cpu-rehearsal",
         "--seconds", "0.5", "--seed", seed, "--trace", "0"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300,
    )


def test_the_system_equals_the_reference_and_both_controls_do_not():
    """Four members, 20,000 nodes, seeded traffic (a seed past 2**31):
    ``correct`` against ``mhref``; ``direct_only`` and ``lost_replay`` each
    come out incorrect; four waves a command, every member's arrays on its
    own device, every member the owner of some command."""
    proc = run_harness(os.path.join(BENCH, "run.py"), "--control", "1", seed="2147483777")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] > 0
    control = line["control"]
    assert control["direct_only"]["correct"] is False
    assert control["lost_replay"]["correct"] is False
    lost = control["lost_replay"]["compared"]
    assert lost["replay_mismatches"][0] == 1 and lost["journal_mismatches"][0] == 0
    notes = line["notes"]
    counters = notes["counters"]
    assert counters["lat_waves"] + counters["lat_overflow_waves"] == 4 * counters["commands"]
    assert counters["external_seen"] == 3 * counters["commands"]
    assert counters["replay_submitted"] == 3 * counters["commands"]
    assert counters["reader_lane_bursts"] == 0
    assert notes["misplaced"] == {"m0": [], "m1": [], "m2": [], "m3": []}
    assert len(set(notes["devices"])) == 4
    assert set(notes["applied_by"]) <= {"m0", "m1", "m2", "m3"}


@pytest.mark.parametrize("fault, seen_by", [
    ("reader_skips_a_record", {"observer_mismatches", "subscriptions_never_fired"}),
    ("all_on_device_0", {"layout_misplaced_arrays"}),
    ("non_owner_executes", {"journal_mismatches", "replay_mismatches"}),
    ("misrouted_is_bounced", {"fallbacks_fired"}),
])
def test_fault_underneath_makes_the_run_incorrect(fault, seen_by):
    proc = run_harness(os.path.join(BENCH, "tests", "mh_fault_run.py"), fault, seed="11")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False, line["compared"]
    over = {name for name, (value, limit) in line["compared"].items() if value > limit}
    if fault == "reader_skips_a_record":
        assert seen_by <= over  # what else differs follows from the lost wave
    else:
        assert over == seen_by


def test_rehearsal_with_too_few_devices_says_what_it_needs():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--cpu-rehearsal", "--seconds", "0.5", "--seed", "11"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=""),
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 2, proc.stderr[-3000:]
    assert "--xla_force_host_platform_device_count=4" in proc.stderr
    assert not proc.stdout.strip()


def test_a_program_that_cannot_place_a_backend_is_refused_at_once():
    """On a checkout whose ``TpuGraphBackend`` takes no device the deployment
    exits 3 with its own line before it generates anything."""
    code = (
        "import sys; sys.path[:0] = [%r, %r]\n"
        "from stl_fusion_tpu.graph import TpuGraphBackend\n"
        "init = TpuGraphBackend.__init__\n"
        "def old(self, hub, node_capacity=4096, edge_capacity=16384):\n"
        "    init(self, hub, node_capacity, edge_capacity)\n"
        "TpuGraphBackend.__init__ = old\n"
        "import run; sys.exit(run.main(sys.argv[1:]))\n" % (BENCH, REPO)
    )
    proc = subprocess.run(
        [sys.executable, "-c", code, "--workload", CELL, "--cpu-rehearsal",
         "--seconds", "0.5", "--seed", "11"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 3, proc.stderr[-3000:]
    assert "takes no device" in proc.stderr and "generating" not in proc.stderr
    assert not proc.stdout.strip()
