"""Nonblocking fused wave execution tests (ISSUE 7 tentpole).

Covers the fused-wave ORACLE-EQUIVALENCE suite — for each fused depth
K ∈ {1, 2, 8} the fused live burst must produce the identical invalid-set
as K sequential waves (checked against both a sequential twin backend and
the resilience host-BFS oracle), including under seeded chaos
(drop/dup/reorder on the client link) and with a mid-chain injected wave
fault degrading to the split host path — plus the WavePipeline's
accumulate/dispatch/drain lifecycle, per-logical-wave
identity through ``explain()`` end-to-end over ``$sys-d`` with the wire
codec on, and the overlap drain counters.
"""
import asyncio

import numpy as np
import pytest

from stl_fusion_tpu.client import compute_client, install_compute_call_type
from stl_fusion_tpu.core import (
    ComputeService,
    FusionHub,
    TableBacking,
    capture,
    compute_method,
    memo_table_of,
    set_default_hub,
)
from stl_fusion_tpu.diagnostics import RECORDER, explain, global_metrics, install_explain
from stl_fusion_tpu.diagnostics.explain import explain_client
from stl_fusion_tpu.graph import TpuGraphBackend, WavePipeline
from stl_fusion_tpu.graph.synthetic import power_law_dag
from stl_fusion_tpu.resilience import ChaosPolicy, ResilienceEvents, WaveWatchdog
from stl_fusion_tpu.rpc import RpcHub, RpcTestTransport, install_compute_fanout

N = 800
SRC, DST = power_law_dag(N, avg_degree=3, seed=7)


class Dag(ComputeService):
    """The test DAG as a table-backed service with a device loader (the
    refresh-chain tests recompute through it)."""

    def __init__(self, hub=None):
        super().__init__(hub)
        self.base = np.arange(N, dtype=np.float32)
        self._base_dev = None

    def load(self, ids):
        return self.base[np.asarray(ids, dtype=np.int64)]

    def load_dev(self, ids, base_dev):
        return base_dev[ids]

    def load_dev_args(self):
        if self._base_dev is None:
            import jax.numpy as jnp

            self._base_dev = jnp.asarray(self.base)
        return (self._base_dev,)

    @compute_method(
        table=TableBacking(
            rows=N, batch="load",
            device_batch="load_dev", device_args="load_dev_args",
        )
    )
    async def node(self, i: int) -> float:
        return float(self.base[i])


def make_stack(build_mirror=True, lat=False):
    """``lat=False`` drops the lat mirror (the state a broken delta log
    leaves): the pipeline then keeps every accumulation on the fused chain,
    which is what this suite is about. The small-wave path that a lat
    mirror opens has its own suite, tests/test_pipeline_lat.py."""
    hub = FusionHub()
    backend = TpuGraphBackend(hub, node_capacity=N + 8, edge_capacity=len(SRC) + 512)
    svc = Dag(hub)
    hub.add_service(svc, "dag")
    table = memo_table_of(svc.node)
    block = backend.bind_table_rows(table)
    backend.declare_row_edges(block, SRC, block, DST)
    table.read_batch(np.arange(N))
    backend.flush()
    if build_mirror:
        backend.graph.build_topo_mirror()
        if not lat:
            backend.graph._topo_mirror["lat"] = None
    return hub, backend, svc, table, block


def wave_seeds(k, rng=None, seeds_per_wave=3):
    rng = rng if rng is not None else np.random.default_rng(20260803)
    return [
        rng.choice(N, size=seeds_per_wave, replace=False).tolist()
        for _ in range(k)
    ]


def host_oracle_invalid_set(backend, wave_seed_lists):
    """The independent host-BFS closure over the live edge set (the
    resilience oracle), applied sequentially per wave from an all-clear
    start — the reference every fused execution must match."""
    graph = backend.graph
    invalid = np.zeros(graph.n_nodes, dtype=bool)
    for seeds in wave_seed_lists:
        newly = WaveWatchdog._host_closure(graph, [seeds], invalid)
        invalid |= newly
    return invalid


# ---------------------------------------------------------------- oracle suite


@pytest.mark.parametrize("k", [1, 2, 8])
async def test_fused_burst_invalid_set_matches_k_sequential_waves(k):
    """THE oracle-equivalence acceptance: a fused chain of depth K leaves
    the identical invalid-set (device AND host mirror) as K sequential
    wave dispatches, and both match the independent host-BFS oracle."""
    seeds = wave_seeds(k)

    # sequential twin: one blocking dispatch per wave
    _hub1, b1, _s1, _t1, blk1 = make_stack()
    seq_counts = [b1.cascade_rows_batch(blk1, w) for w in seeds]

    # fused: all K waves through the pipeline in one chain
    hub2, b2, _s2, _t2, blk2 = make_stack()
    pipe = hub2.enable_nonblocking(fuse_depth=k)
    tickets = [pipe.submit_rows(blk2, w) for w in seeds]
    pipe.drain()

    assert pipe.stats()["eager_waves"] == 0  # the fused path served it
    for i, t in enumerate(tickets):
        assert t.done and t.count == seq_counts[i], (i, t.count, seq_counts[i])
    assert np.array_equal(b1.graph._h_invalid, b2.graph._h_invalid)
    assert np.array_equal(
        np.asarray(b1.graph.invalid_mask()), np.asarray(b2.graph.invalid_mask())
    )
    oracle = host_oracle_invalid_set(b2, seeds)
    assert np.array_equal(np.asarray(b2.graph.invalid_mask()), oracle)


async def test_fused_depth_identity_recorded():
    """A fused dispatch stamps a span of seqs, the profiler record carries
    fused_depth + seq_span, and the engagement histogram is non-empty with
    p50 > 1 (the CI gate's source of truth)."""
    hub, backend, _svc, _table, block = make_stack()
    # the registry histogram is process-global (other tests' depth-1 burst
    # dispatches record into it too): snapshot-and-diff isolates THIS
    # test's samples, the same way the perf harnesses do
    hist = global_metrics().histogram(
        "fusion_wave_fused_depth", unit="waves", lo=1.0, hi=4096.0
    )
    ck = hist.checkpoint()
    pipe = hub.enable_nonblocking(fuse_depth=4)
    tickets = [pipe.submit_rows(block, w) for w in wave_seeds(4)]
    pipe.drain()
    rec = backend.profiler.recent()[-1]
    assert rec["kind"] == "pipeline" and rec["fused_depth"] == 4
    s0, s1 = rec["seq_span"]
    assert s1 - s0 == 3
    assert [t.seq for t in tickets] == list(range(s0, s1 + 1))
    summary = backend.profiler.summary()
    assert summary["fused_dispatches"] >= 1
    delta = hist.since(ck)
    assert delta["count"] >= 1 and delta["p50"] is not None and delta["p50"] > 1


async def test_accumulator_batches_submits_between_dispatches():
    """The lazy accumulator: submits below fuse_depth stay pending (no
    dispatch, nodes still consistent — the nonblocking contract) until
    the threshold or an explicit drain."""
    hub, backend, svc, table, block = make_stack()
    pipe = hub.enable_nonblocking(fuse_depth=8)
    before = backend.graph.mirror_bursts
    for w in wave_seeds(3):
        pipe.submit_rows(block, w)
    assert pipe.stats()["pending_waves"] == 3
    assert backend.graph.mirror_bursts == before  # nothing dispatched
    assert table.stale_count() == 0  # nonblocking: not applied yet
    pipe.drain()
    assert pipe.stats()["pending_waves"] == 0
    assert table.stale_count() > 0


async def test_invalidate_eventually_rides_pipeline_and_falls_back():
    """Computed.invalidate_eventually: with a pipeline attached the node
    stays consistent until the drain barrier; without one it degrades to
    an immediate invalidate."""
    hub, backend, svc, table, block = make_stack()
    node = await capture(lambda: svc.node(5))
    assert node.is_consistent

    pipe = hub.enable_nonblocking(fuse_depth=8)
    assert node.invalidate_eventually()
    assert node.is_consistent  # lazily accumulated, not applied
    pipe.drain()
    assert node.is_invalidated

    # no pipeline: immediate
    pipe.dispose()
    node2 = await capture(lambda: svc.node(700))
    assert node2.invalidate_eventually()
    assert node2.is_invalidated


async def test_journal_entry_with_inflight_chain_forces_harvest_first():
    """A host-led table mark journaled while a chain is in flight: the next
    dispatch must harvest the chain BEFORE flushing — flush's icasc
    expansion reads the host invalid mirror (was_clear), and a stale
    mirror would clear a device bit the chain just set (a silently
    dropped cascade). Final state must match the fully-sequential twin."""
    hub, backend, _svc, table, block = make_stack()
    _hub2, b2, _s2, t2, blk2 = make_stack()
    pipe = hub.enable_nonblocking(fuse_depth=1)
    pipe.submit_rows(block, [0])  # depth 1: dispatches immediately
    assert pipe.stats()["inflight_chains"] == 1
    # a row inside the in-flight closure, marked host-side mid-flight
    row = int(DST[SRC == 0][0]) if (SRC == 0).any() else 1
    table.invalidate(np.array([row]))
    assert backend._journal  # the hazard precondition (icasc pending)
    pipe.submit_rows(block, [5])  # dispatch: must harvest chain 1 first
    pipe.drain()
    b2.cascade_rows_batch(blk2, [0])
    t2.invalidate(np.array([row]))
    b2.flush()
    b2.cascade_rows_batch(blk2, [5])
    assert np.array_equal(backend.graph._h_invalid, b2.graph._h_invalid)
    assert np.array_equal(
        np.asarray(backend.graph.invalid_mask()),
        np.asarray(b2.graph.invalid_mask()),
    )


# ---------------------------------------------------------------- fault path


async def test_mid_chain_injected_fault_degrades_to_split_host_path():
    """A wave fault injected into the fused chain (the chaos hook) is
    CONTAINED: the waves re-run on the split host loop, the watchdog
    degrades then recovers, and the final invalid-set still matches the
    sequential twin and the host-BFS oracle."""
    seeds = wave_seeds(4, rng=np.random.default_rng(5))
    _hub1, b1, _s1, _t1, blk1 = make_stack()
    seq_counts = [b1.cascade_rows_batch(blk1, w) for w in seeds]

    hub2, b2, _s2, _t2, blk2 = make_stack()
    events = ResilienceEvents()
    wd = b2.attach_watchdog(WaveWatchdog(recovery_bursts=1, events=events))
    pipe = hub2.enable_nonblocking(fuse_depth=4)
    wd.inject_fault_next()
    tickets = [pipe.submit_rows(blk2, w) for w in seeds]
    pipe.drain()

    assert pipe.stats()["chain_faults"] == 1
    assert wd.faults == 1 and wd.mode == WaveWatchdog.MODE_FUSED  # recovered
    assert events.count("wave_fault") == 1
    for i, t in enumerate(tickets):
        assert t.done and t.count == seq_counts[i], (i, t.count, seq_counts[i])
    assert np.array_equal(b1.graph._h_invalid, b2.graph._h_invalid)
    oracle = host_oracle_invalid_set(b2, seeds)
    assert np.array_equal(np.asarray(b2.graph.invalid_mask()), oracle)


async def test_harvest_fault_contained_to_host_path(monkeypatch):
    """A fault AFTER dispatch (the readback half of the chain) is contained
    the same way: host re-run, identical final state."""
    seeds = wave_seeds(3, rng=np.random.default_rng(6))
    _hub1, b1, _s1, _t1, blk1 = make_stack()
    for w in seeds:
        b1.cascade_rows_batch(blk1, w)

    hub2, b2, _s2, _t2, blk2 = make_stack()
    pipe = hub2.enable_nonblocking(fuse_depth=8)
    real = type(b2.graph).harvest_waves_lanes_chain
    state = {"fail": True}

    def flaky(self, pending):
        if state.pop("fail", None):
            raise RuntimeError("injected harvest fault")
        return real(self, pending)

    monkeypatch.setattr(type(b2.graph), "harvest_waves_lanes_chain", flaky)
    for w in seeds:
        pipe.submit_rows(blk2, w)
    pipe.drain()
    assert pipe.stats()["chain_faults"] == 1
    assert np.array_equal(b1.graph._h_invalid, b2.graph._h_invalid)


async def test_degraded_watchdog_routes_pipeline_to_host_loop():
    """While the watchdog is in host mode, pipeline dispatches run the
    split host loop and count toward the recovery window."""
    seeds = wave_seeds(2, rng=np.random.default_rng(8))
    hub, backend, _svc, _table, block = make_stack()
    wd = backend.attach_watchdog(
        WaveWatchdog(recovery_bursts=2, events=ResilienceEvents())
    )
    wd._degrade("wave_fault", "test")
    pipe = hub.enable_nonblocking(fuse_depth=2)
    for w in seeds:
        pipe.submit_rows(block, w)
    pipe.drain()
    assert pipe.stats()["eager_waves"] == 2
    assert wd.fallbacks >= 1
    oracle = host_oracle_invalid_set(backend, seeds)
    assert np.array_equal(np.asarray(backend.graph.invalid_mask()), oracle)


# ---------------------------------------------------------------- chaos + rpc


def _make_rpc_stack(chaos=None):
    hub, backend, svc, table, block = make_stack()
    server_rpc = RpcHub("server")
    install_compute_call_type(server_rpc)
    server_rpc.add_service("dag", svc)
    install_compute_fanout(server_rpc, backend)
    install_explain(server_rpc, fusion_hub=hub)
    client_fusion = FusionHub()
    client_rpc = RpcHub("client")
    install_compute_call_type(client_rpc)
    install_explain(client_rpc)
    transport = RpcTestTransport(client_rpc, server_rpc, wire_codec=True)
    if chaos is not None:
        transport.set_chaos(chaos)
    client = compute_client("dag", client_rpc, client_fusion)
    return (
        hub, backend, svc, table, block,
        server_rpc, client_rpc, client, transport, client_fusion,
    )


async def _stop(*hubs):
    for h in hubs:
        await h.stop()


@pytest.mark.parametrize("k", [2, 8])
async def test_fused_burst_under_seeded_chaos_converges(k):
    """Fused chains under drop/dup/reorder chaos on the client link: the
    invalid-set still matches the host-BFS oracle exactly, and every
    subscribed client key fences despite the chaos (the coalescer's
    reconnect-riding machinery, unchanged by fusion)."""
    policy = ChaosPolicy(
        seed=42, drop=0.05, duplicate=0.1, reorder_window=4,
        reorder_flush_s=0.005,
    )
    (
        hub, backend, _svc, _table, block,
        server_rpc, client_rpc, client, transport, _cf,
    ) = _make_rpc_stack(chaos=policy)
    try:
        # subscribe a few deep keys (high ids: closure targets)
        keys = [N - 1 - i for i in range(4)]
        nodes = []
        for key in keys:
            assert await client.node(int(key)) == float(key)
            nodes.append(await capture(lambda key=key: client.node(int(key))))
        seeds = wave_seeds(k, rng=np.random.default_rng(21))
        seeds[0] = [0]  # the root: its closure reaches the subscribed tail
        pipe = hub.enable_nonblocking(fuse_depth=k)
        for w in seeds:
            pipe.submit_rows(block, w)
        pipe.drain()
        assert pipe.stats()["eager_waves"] == 0
        oracle = host_oracle_invalid_set(backend, seeds)
        assert np.array_equal(np.asarray(backend.graph.invalid_mask()), oracle)
        # chaos may drop frames WITH the link; the outbox re-pends across
        # reconnects — every subscribed key in the closure must fence
        fenced = [
            nd for nd, key in zip(nodes, keys) if oracle[key]
        ]
        assert fenced, "test graph produced no subscribed closure hits"
        await asyncio.wait_for(
            asyncio.gather(*(nd.when_invalidated() for nd in fenced)), 15.0
        )
    finally:
        transport.set_chaos(None)
        await _stop(client_rpc, server_rpc)


async def test_overlap_drain_counts_fences_inside_flight_window():
    """With two chains in flight back-to-back, the first chain's fence
    drain runs while the second executes — the fan-out index counts it
    under drained_overlapped and the pipeline reports overlap occupancy."""
    (
        hub, backend, _svc, _table, block,
        server_rpc, client_rpc, client, _transport, _cf,
    ) = _make_rpc_stack()
    try:
        keys = [N - 1 - i for i in range(3)]
        nodes = []
        for key in keys:
            assert await client.node(int(key)) == float(key)
            nodes.append(await capture(lambda key=key: client.node(int(key))))
        pipe = hub.enable_nonblocking(fuse_depth=1)
        # chain 1 fences the subscriptions (root seed); chain 2 dispatches
        # before chain 1 is harvested (MAX_INFLIGHT=1 → the harvest of 1
        # happens during 2's flight window)
        pipe.submit_rows(block, [0])
        pipe.submit_rows(block, [1])
        pipe.drain()
        index = server_rpc.compute_fanout
        assert index.drained_total >= len(keys)
        assert index.drained_overlapped >= 1, index.stats()
        assert pipe.stats()["overlap_harvests"] >= 1
        assert pipe.overlap_occupancy() > 0.0
        await asyncio.wait_for(
            asyncio.gather(*(nd.when_invalidated() for nd in nodes)), 10.0
        )
    finally:
        await _stop(client_rpc, server_rpc)


# ---------------------------------------------------------------- explain


CHAIN_N = 30


def _make_three_chains():
    """Three DISJOINT 10-row chains in one table: each logical wave of the
    fused dispatch owns one chain, so a key's fencing wave is knowable."""
    hub = FusionHub()
    backend = TpuGraphBackend(hub, node_capacity=CHAIN_N + 8, edge_capacity=256)

    class Tbl(ComputeService):
        def __init__(self, h=None):
            super().__init__(h)
            self.base = np.arange(CHAIN_N, dtype=np.float32)

        def load(self, ids):
            return self.base[np.asarray(ids, dtype=np.int64)]

        @compute_method(table=TableBacking(rows=CHAIN_N, batch="load"))
        async def node(self, i: int) -> float:
            return float(self.base[i])

    svc = Tbl(hub)
    hub.add_service(svc, "tbl")
    table = memo_table_of(svc.node)
    block = backend.bind_table_rows(table)
    src = np.concatenate([np.arange(c * 10, c * 10 + 9) for c in range(3)])
    dst = src + 1
    backend.declare_row_edges(block, src, block, dst)
    table.read_batch(np.arange(CHAIN_N))
    backend.flush()
    backend.graph.build_topo_mirror()
    backend.graph._topo_mirror["lat"] = None  # keep the waves on the chain
    return hub, backend, svc, table, block


async def test_explain_names_logical_wave_inside_fused_chain():
    """explain(key) must name the LOGICAL wave that fenced the key — its
    own seq — even though it was physically fused into a chain, and say
    so (chain span + depth) in the human-readable line."""
    hub, backend, svc, _table, block = _make_three_chains()
    # watch a key in the SECOND chain so its invalidation is applied
    # eagerly (recorder event carries the stage's wave seq)
    target = await capture(lambda: svc.node(15))
    target.on_invalidated(lambda c: None)
    pipe = hub.enable_nonblocking(fuse_depth=3)
    tickets = [pipe.submit_rows(block, [c * 10]) for c in range(3)]
    pipe.drain()
    assert tickets[1].seq is not None
    report = explain(target, hub=hub)
    inv = report["invalidation"]
    assert inv["wave_seq"] == tickets[1].seq, (inv, tickets[1].seq)
    rec = inv["wave"]
    assert rec is not None and rec["fused_depth"] == 3
    assert rec["seq_span"] == [tickets[0].seq, tickets[2].seq]
    head = report["chain"][0]
    assert f"wave #{tickets[1].seq}" in head and "fused into chain" in head, head
    assert f"#{tickets[0].seq}–#{tickets[2].seq}" in head, head


async def test_explain_fused_wave_end_to_end_over_sys_d():
    """The acceptance hop: a CLIENT's key fenced by a wave that was
    physically fused into a chain — explain_client over ``$sys-d`` (wire
    codec on) returns the server chain naming the correct logical wave
    and the chain cause id the client's own fence recorded."""
    hub, backend, svc, _table, block = _make_three_chains()
    server_rpc = RpcHub("server")
    install_compute_call_type(server_rpc)
    server_rpc.add_service("tbl", svc)
    install_compute_fanout(server_rpc, backend)
    install_explain(server_rpc, fusion_hub=hub)
    client_rpc = RpcHub("client")
    install_compute_call_type(client_rpc)
    install_explain(client_rpc)
    RpcTestTransport(client_rpc, server_rpc, wire_codec=True)
    client = compute_client("tbl", client_rpc, FusionHub())
    try:
        assert await client.node(15) == 15.0
        node = await capture(lambda: client.node(15))
        pipe = hub.enable_nonblocking(fuse_depth=3)
        tickets = [pipe.submit_rows(block, [c * 10]) for c in range(3)]
        pipe.drain()
        await asyncio.wait_for(node.when_invalidated(), 10.0)
        both = await explain_client(node)
        remote = both["remote"]
        inv = remote["invalidation"]
        assert inv["cause"] == node.invalidation_cause, (inv, node.invalidation_cause)
        assert inv["wave_seq"] == tickets[1].seq, (inv, tickets[1].seq)
        assert inv["wave"]["fused_depth"] == 3
        head = remote["chain"][0]
        assert f"wave #{tickets[1].seq}" in head and "fused into chain" in head, head
        # the client's local half recorded the same fence cause
        local_inv = both["local"]["invalidation"]
        assert local_inv["cause"] == node.invalidation_cause
    finally:
        await _stop(client_rpc, server_rpc)


# ---------------------------------------------------------------- outbox batch


async def test_outbox_batch_post_merges_under_one_kick():
    """PeerOutbox.post_invalidations: N entries merge into the pending map
    (version-deduped, last wins) and flush as one batch frame."""
    hub, backend, svc, _table, _block = make_stack(build_mirror=False)
    server_rpc = RpcHub("server")
    install_compute_call_type(server_rpc)
    server_rpc.add_service("dag", svc)
    client_rpc = RpcHub("client")
    install_compute_call_type(client_rpc)
    RpcTestTransport(client_rpc, server_rpc, wire_codec=True)
    client = compute_client("dag", client_rpc, FusionHub())
    try:
        assert await client.node(3) == 3.0
        node = await capture(lambda: client.node(3))
        (peer,) = server_rpc.peers.values()
        call_id = node.call.call_id
        peer.outbox.post_invalidations(
            [
                (call_id, "@stale", None, None),
                (call_id, node.version.format(), None, None),  # last wins
            ]
        )
        await asyncio.wait_for(node.when_invalidated(), 5.0)
        stats = peer.outbox.stats()
        assert stats["invalidations_posted"] >= 2
        assert stats["invalidations_coalesced"] >= 1
        assert stats["batch_frames_sent"] >= 1
    finally:
        await _stop(client_rpc, server_rpc)
