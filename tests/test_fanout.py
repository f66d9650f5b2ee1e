"""Coalesced invalidation fan-out tests (ISSUE 2 tentpole).

Covers the per-peer outbox (FIFO drain + invalidation coalescing), the
``$sys-c.invalidate_batch`` frame (delivery, chaos convergence, interaction
with the PR-1 redelivered-result version-mismatch rule), the newly-mask →
subscribed-key fanout index over a live TpuGraphBackend, per-peer FIFO
ordering across reconnects, and the FusionMonitor counter export. This file
is the tier-1 smoke for the whole coalescer path — none of it is
slow-marked.
"""
import asyncio

import numpy as np
import pytest

from stl_fusion_tpu.client import compute_client, install_compute_call_type
from stl_fusion_tpu.core import (
    ComputeService,
    FusionHub,
    capture,
    compute_method,
    invalidating,
    set_default_hub,
)
from stl_fusion_tpu.diagnostics import FusionMonitor, validate_hub
from stl_fusion_tpu.graph import TpuGraphBackend
from stl_fusion_tpu.rpc import RpcHub, RpcTestTransport, install_compute_fanout
from stl_fusion_tpu.rpc.message import COMPUTE_SYSTEM_SERVICE


class CounterService(ComputeService):
    def __init__(self, hub=None):
        super().__init__(hub)
        self.counters = {}
        self.compute_count = 0

    @compute_method
    async def get(self, key: str) -> int:
        self.compute_count += 1
        return self.counters.get(key, 0)

    async def increment(self, key: str):
        self.counters[key] = self.counters.get(key, 0) + 1
        with invalidating():
            await self.get(key)


def make_stack(wire_codec=False, coalesce=True):
    server_fusion = FusionHub()
    client_fusion = FusionHub()
    server_rpc = RpcHub("server")
    server_rpc.coalesce_invalidations = coalesce
    client_rpc = RpcHub("client")
    install_compute_call_type(server_rpc)
    install_compute_call_type(client_rpc)
    svc = CounterService(server_fusion)
    server_rpc.add_service("counters", svc)
    transport = RpcTestTransport(client_rpc, server_rpc, wire_codec=wire_codec)
    client = compute_client("counters", client_rpc, client_fusion)
    return svc, client, transport, client_rpc, server_rpc, client_fusion


async def _stop(*hubs):
    for h in hubs:
        await h.stop()


def _server_peer(server_rpc):
    (peer,) = server_rpc.peers.values()
    return peer


# ---------------------------------------------------------------- batch frames


async def test_invalidation_rides_batch_frames_by_default():
    """With coalescing on (the default), a server-side invalidation reaches
    the client as a $sys-c.invalidate_batch frame, not a per-key frame —
    and still cascades through the client graph."""
    svc, client, _t, crpc, srpc, cf = make_stack()
    try:
        assert await client.get("a") == 0
        node = await capture(lambda: client.get("a"))
        await svc.increment("a")
        await asyncio.wait_for(node.when_invalidated(), 5.0)
        assert await client.get("a") == 1
        stats = srpc.fanout_stats()
        assert stats["batch_frames_sent"] >= 1
        assert stats["batch_keys_sent"] >= 1
        assert stats["invalidations_posted"] >= 1
    finally:
        await _stop(crpc, srpc)


async def test_many_keys_coalesce_into_few_frames():
    """N keys invalidated back-to-back before the drain runs ship as ONE
    version-deduped batch frame (the coalescing contract), while per-key
    mode ships N frames."""
    svc, client, _t, crpc, srpc, cf = make_stack()
    try:
        keys = [f"k{i}" for i in range(12)]
        nodes = {}
        for k in keys:
            assert await client.get(k) == 0
            nodes[k] = await capture(lambda k=k: client.get(k))
        # invalidate all keys in one loop slice: the sync handlers post into
        # the outbox pending map before its drain task gets to run
        for k in keys:
            svc.counters[k] = 1
            with invalidating():
                await svc.get(k)
        await asyncio.gather(
            *(asyncio.wait_for(nodes[k].when_invalidated(), 5.0) for k in keys)
        )
        stats = _server_peer(srpc)._outbox.stats()
        assert stats["batch_keys_sent"] == len(keys)
        # all 12 posts flushed in far fewer frames than keys (typically 1)
        assert stats["batch_frames_sent"] <= 3
        for k in keys:
            assert await client.get(k) == 1
    finally:
        await _stop(crpc, srpc)


async def test_batch_entry_for_unknown_call_is_ignored():
    """A dup/reordered batch frame naming an already-retired call id must
    no-op (the client re-subscribed under a new call id)."""
    svc, client, _t, crpc, srpc, cf = make_stack()
    try:
        assert await client.get("a") == 0
        node = await capture(lambda: client.get("a"))
        await svc.increment("a")
        await asyncio.wait_for(node.when_invalidated(), 5.0)
        assert await client.get("a") == 1  # re-subscribed
        node2 = await capture(lambda: client.get("a"))
        # replay a forged stale batch frame for long-gone call ids
        from stl_fusion_tpu.rpc.message import CALL_TYPE_COMPUTE, RpcMessage
        from stl_fusion_tpu.utils.serialization import dumps

        peer = crpc.peers["default"]
        await peer.process_message(
            RpcMessage(
                CALL_TYPE_COMPUTE, 0, COMPUTE_SYSTEM_SERVICE, "invalidate_batch",
                dumps([[[99991, "@7"], [99992, None]]]),
            )
        )
        await asyncio.sleep(0.05)
        assert node2.is_consistent  # fresh subscription untouched
        assert await client.get("a") == 1
    finally:
        await _stop(crpc, srpc)


# ---------------------------------------------------------------- chaos


@pytest.mark.parametrize("coalesce", [True, False])
async def test_batch_delivery_chaos_dup_reorder_converges(coalesce):
    """Duplicated + reordered frames (resilience.ChaosPolicy on the twisted
    channels) with mid-subscription disconnects: batched delivery must
    converge to the same client state as per-key delivery — every
    increment still reaches the client, duplicates no-op."""
    from stl_fusion_tpu.resilience import ChaosPolicy

    svc, client, transport, crpc, srpc, _cf = make_stack(coalesce=coalesce)
    policy = ChaosPolicy(seed=42, duplicate=0.5, reorder_window=4, reorder_flush_s=0.005)
    transport.set_chaos(policy)
    try:
        assert await client.get("a") == 0
        node = await capture(lambda: client.get("a"))
        await transport.disconnect()
        await transport.wait_connected()
        await svc.increment("a")
        await asyncio.wait_for(node.when_invalidated(), 5.0)
        assert await client.get("a") == 1
        for expect in (2, 3, 4):
            node = await capture(lambda: client.get("a"))
            await svc.increment("a")
            await asyncio.wait_for(node.when_invalidated(), 5.0)
            assert await client.get("a") == expect
        assert policy.duplicated > 0
        if coalesce:
            assert srpc.fanout_stats()["batch_frames_sent"] >= 1
        # correctness sweep after the chaos (ISSUE 4 satellite: the race-
        # detection story must RUN in the suites, not just exist): the
        # hammered server graph — and the client mirror of it — still
        # satisfies I1-I5
        validate_hub(svc._fusion_hub).require()
        validate_hub(_cf).require()
    finally:
        await _stop(crpc, srpc)


async def test_dropped_batch_frame_converges_after_reconnect():
    """A batch frame lost WITH its link (the reliable-transport drop shape)
    must not strand the client stale: the outbox re-pends the batch across
    the reconnect AND the re-sent call gets a version-mismatch / restart
    answer — either path must converge. Uses the chaos channel wrapper so
    the drop kills the link exactly like packet loss on TCP."""
    from stl_fusion_tpu.resilience import ChaosPolicy

    for seed in (3, 11, 29):
        svc, client, transport, crpc, srpc, _cf = make_stack()
        policy = ChaosPolicy(seed=seed, drop=0.08, duplicate=0.05, reorder_window=3)
        transport.set_chaos(policy)
        try:
            keys = ["a", "b", "c"]
            for k in keys:
                assert await client.get(k) == 0
            for _ in range(12):
                for k in keys:
                    await svc.increment(k)
                await asyncio.sleep(0.01)
            # chaos off for convergence check (fresh links are clean)
            transport.set_chaos(None)
            loop = asyncio.get_event_loop()
            for k in keys:
                want = svc.counters[k]
                deadline = loop.time() + 10.0
                while True:
                    got = await client.get(k)
                    if got == want:
                        break
                    assert loop.time() < deadline, (
                        f"seed {seed}: stuck at {k}={got}, server={want} — "
                        f"a batched invalidation was lost"
                    )
                    await asyncio.sleep(0.05)
            # structural invariants held through drops + reconnects
            validate_hub(svc._fusion_hub).require()
            validate_hub(_cf).require()
        finally:
            await _stop(crpc, srpc)


async def test_redelivered_result_version_mismatch_still_invalidate(
):
    """PR-1 interaction: a redelivered result whose @version moved on while
    the link was down must invalidate the bound computed even when the
    original invalidation (now batched) died with the old link."""
    svc, client, transport, crpc, srpc, _cf = make_stack()
    try:
        assert await client.get("v") == 0
        node = await capture(lambda: client.get("v"))
        transport.block_reconnects(True)
        await transport.disconnect()
        # server recomputes while the link is down: the batched invalidation
        # for the client's version is pending in the outbox, the new result
        # has a new version
        await svc.increment("v")
        await asyncio.sleep(0.05)
        transport.block_reconnects(False)
        # reconnect: client re-sends the registered call; whichever arrives
        # first (re-flushed batch or version-mismatched redelivery), the
        # node must invalidate and converge
        await asyncio.wait_for(node.when_invalidated(), 5.0)
        loop = asyncio.get_event_loop()
        deadline = loop.time() + 5.0
        while await client.get("v") != 1:
            assert loop.time() < deadline
            await asyncio.sleep(0.05)
    finally:
        await _stop(crpc, srpc)


# ---------------------------------------------------------------- FIFO order


async def test_outbox_preserves_per_peer_fifo_across_reconnect():
    """Regression (ISSUE 2 satellite): concurrent senders' messages reach
    the wire in enqueue order, and the order survives a reconnect — the
    pre-outbox send() interleaved concurrent senders on the raw channel."""
    server_rpc = RpcHub("server")
    client_rpc = RpcHub("client")

    received = []

    class Echo:
        async def note(self, i):
            received.append(i)
            return i

    server_rpc.add_service("echo", Echo())
    transport = RpcTestTransport(client_rpc, server_rpc)
    try:
        proxy = client_rpc.client("echo")
        assert await proxy.note(-1) == -1  # connect
        peer = client_rpc.peers["default"]

        # burst of concurrent fire-and-forget sends: enqueue order 0..39
        from stl_fusion_tpu.rpc.calls import RpcOutboundCall

        async def send_one(i):
            call = RpcOutboundCall(peer, "echo", "note", (i,), no_wait=True)
            peer.outbound_calls[call.call_id] = call  # keep id order stable
            await peer.send(call.to_message())

        await asyncio.gather(*(send_one(i) for i in range(20)))
        await transport.disconnect()
        await transport.wait_connected()
        await asyncio.gather(*(send_one(i) for i in range(20, 40)))

        deadline = asyncio.get_event_loop().time() + 5.0
        while len([r for r in received if r >= 0]) < 40:
            assert asyncio.get_event_loop().time() < deadline, received
            await asyncio.sleep(0.02)
        seq = [r for r in received if r >= 0]
        # dedup re-sent duplicates (reconnect re-delivery), keep first sight
        seen, order = set(), []
        for r in seq:
            if r not in seen:
                seen.add(r)
                order.append(r)
        assert order == sorted(order), f"FIFO violated: {order}"
    finally:
        await _stop(client_rpc, server_rpc)


# ---------------------------------------------------------------- fanout index


async def test_fanout_index_drains_newly_mask_to_batches():
    """End-to-end tentpole smoke on a live graph: table-backed service,
    device cascade, newly set drains through the ComputeFanoutIndex into
    one batch frame per peer; clients observe the invalidation."""
    n = 64
    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        from stl_fusion_tpu.core import TableBacking, memo_table_of

        backend = TpuGraphBackend(hub, node_capacity=n + 8, edge_capacity=256)

        class Tbl(ComputeService):
            def __init__(self, h=None):
                super().__init__(h)
                self.base = np.arange(n, dtype=np.float32)

            def load(self, ids):
                return self.base[np.asarray(ids, dtype=np.int64)]

            @compute_method(table=TableBacking(rows=n, batch="load"))
            async def node(self, i: int) -> float:
                return float(self.base[i])

        svc = Tbl(hub)
        hub.add_service(svc, "tbl")
        table = memo_table_of(svc.node)
        block = backend.bind_table_rows(table)
        src = np.arange(0, n - 1, dtype=np.int64)
        dst = np.arange(1, n, dtype=np.int64)  # a chain 0 -> 1 -> ... -> n-1
        backend.declare_row_edges(block, src, block, dst)
        table.read_batch(np.arange(n))
        backend.flush()

        server_rpc = RpcHub("server")
        install_compute_call_type(server_rpc)
        server_rpc.add_service("tbl", svc)
        index = install_compute_fanout(server_rpc, backend)

        client_fusion = FusionHub()
        client_rpc = RpcHub("client")
        install_compute_call_type(client_rpc)
        RpcTestTransport(client_rpc, server_rpc)
        client = compute_client("tbl", client_rpc, client_fusion)
        try:
            assert await client.node(n - 1) == float(n - 1)
            node = await capture(lambda: client.node(n - 1))
            assert index.subscriptions == 1
            # cascade from row 0: the chain reaches row n-1, the mask drain
            # must fence the subscription without any watch-task send
            backend.cascade_rows_batch(block, [0])
            await asyncio.wait_for(node.when_invalidated(), 5.0)
            assert index.subscriptions == 0
            assert index.drained_total == 1
            stats = server_rpc.fanout_stats()
            assert stats["batch_frames_sent"] >= 1
            assert stats["fanout_index"]["drained_total"] == 1

            # wire-compat mode: with coalescing OFF the installed index
            # must stand down — delivery reverts to per-key frames an old
            # client can parse, and nothing registers into the index
            table.read_batch(np.arange(n))
            backend.flush()
            backend.graph.clear_invalid()
            server_rpc.coalesce_invalidations = False
            assert await client.node(n - 1) == float(n - 1)
            node = await capture(lambda: client.node(n - 1))
            assert index.subscriptions == 0  # registration gated on flag
            frames_before = server_rpc.fanout_stats()["batch_frames_sent"]
            backend.cascade_rows_batch(block, [0])
            await asyncio.wait_for(node.when_invalidated(), 5.0)
            assert server_rpc.fanout_stats()["batch_frames_sent"] == frames_before
        finally:
            await _stop(client_rpc, server_rpc)
    finally:
        set_default_hub(old)


def test_group_commuting_entries_keeps_the_bump_epack_rules():
    """The flush pre-pass: alternating distinct-nid bump/epack pairs regroup
    into runs; repeated nids and foreign kinds end a run in place."""
    coalesce = TpuGraphBackend._group_commuting_entries

    def ep(nid, srcs=(5,)):
        return (
            "epack",
            (np.asarray(srcs, np.int32), np.full(len(srcs), nid, np.int32)),
        )

    j = [("bump", 1), ep(1), ("bump", 2), ep(2), ("bump", 3), ep(3)]
    out = coalesce(list(j))
    assert [k for k, _ in out] == ["bump"] * 3 + ["epack"] * 3
    assert [p for k, p in out if k == "bump"] == [1, 2, 3]

    # repeated nid: the second pair must stay AFTER the first pair's epack
    j = [("bump", 1), ep(1), ("bump", 1), ep(1)]
    out = coalesce(list(j))
    assert [k for k, _ in out] == ["bump", "epack", "bump", "epack"]

    # a foreign kind ends the run without being moved
    j = [("bump", 1), ep(1), ("bump", 2), ep(2), ("invalid", 7), ("bump", 3), ep(3)]
    out = coalesce(list(j))
    kinds = [k for k, _ in out]
    assert kinds == ["bump", "bump", "epack", "epack", "invalid", "bump", "epack"]


async def test_recompute_storm_flush_equivalent_to_sequential():
    """End-to-end: N scalar recomputes (bump + in-edge redeclare pairs) in
    ONE flush — the re-subscription storm shape — must leave the same
    cascade behavior as flushing per recompute."""
    from stl_fusion_tpu.core import TableBacking, invalidating, memo_table_of

    n = 48
    for flush_each in (True, False):
        hub = FusionHub()
        old = set_default_hub(hub)
        try:
            backend = TpuGraphBackend(hub, node_capacity=n + 8, edge_capacity=512)

            class Tbl(ComputeService):
                def __init__(self, h=None):
                    super().__init__(h)
                    self.base = np.arange(n, dtype=np.float32)

                def load(self, ids):
                    return self.base[np.asarray(ids, dtype=np.int64)]

                @compute_method(table=TableBacking(rows=n, batch="load"))
                async def node(self, i: int) -> float:
                    return float(self.base[i])

            svc = Tbl(hub)
            hub.add_service(svc, "tbl")
            table = memo_table_of(svc.node)
            block = backend.bind_table_rows(table)
            src = np.arange(0, n - 1, dtype=np.int64)
            dst = np.arange(1, n, dtype=np.int64)  # chain 0 → ... → n-1
            backend.declare_row_edges(block, src, block, dst)
            table.read_batch(np.arange(n))
            backend.flush()

            # recompute a spread of rows: each journals (bump, epack)
            for i in (3, 9, 20, 21, 40):
                with invalidating():
                    await svc.node(i)
                await svc.node(i)
                if flush_each:
                    backend.flush()
            table.read_batch(np.arange(n))  # restore consistency
            backend.flush()
            backend.graph.clear_invalid()
            # the declared chain must have survived the redeclares: a
            # cascade from row 0 still closes over the whole chain
            count = backend.cascade_rows_batch(block, [0])
            assert count == n, (flush_each, count)
        finally:
            set_default_hub(old)


# ---------------------------------------------------------------- diagnostics


async def test_device_burst_fences_remote_table_subscribers():
    """Gap closed by this PR: rows a DEVICE WAVE marks stale used to stay
    silent toward $sys-t subscribers (the wave path never fired
    on_invalidate) — a RemoteTable client kept serving its cached rows
    forever. The backend's on_wave_invalidate hook now fences them."""
    from stl_fusion_tpu.client.remote_table import RemoteTable, RemoteTableHost
    from stl_fusion_tpu.core import TableBacking, memo_table_of

    n = 32
    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub, node_capacity=n + 8, edge_capacity=128)

        class Tbl(ComputeService):
            def __init__(self, h=None):
                super().__init__(h)
                self.base = np.arange(n, dtype=np.float32)

            def load(self, ids):
                return self.base[np.asarray(ids, dtype=np.int64)]

            @compute_method(table=TableBacking(rows=n, batch="load"))
            async def node(self, i: int) -> float:
                return float(self.base[i])

        svc = Tbl(hub)
        hub.add_service(svc, "tbl")
        table = memo_table_of(svc.node)
        block = backend.bind_table_rows(table)
        backend.declare_row_edges(
            block, np.arange(0, n - 1), block, np.arange(1, n)
        )
        table.read_batch(np.arange(n))
        backend.flush()

        server_rpc = RpcHub("server")
        client_rpc = RpcHub("client")
        RpcTestTransport(client_rpc, server_rpc)
        RemoteTableHost(server_rpc).expose("t", table)
        remote = RemoteTable(client_rpc, "default", "t")
        try:
            vals = await remote.read_batch(np.arange(n))
            assert float(vals[n - 1]) == float(n - 1)
            fences0 = remote.fences_seen
            # device cascade from row 0 closes over the whole chain; the
            # wave hook must push a $sys-t fence to the subscribed client
            backend.cascade_rows_batch(block, [0])
            deadline = asyncio.get_event_loop().time() + 5.0
            while remote.fences_seen == fences0:
                assert asyncio.get_event_loop().time() < deadline, (
                    "burst-stale rows never fenced the remote table client"
                )
                await asyncio.sleep(0.02)
            assert not remote._valid[n - 1]  # the cached row went stale
        finally:
            remote.dispose()
            await _stop(client_rpc, server_rpc)
    finally:
        set_default_hub(old)


async def test_monitor_exports_coalescer_counters():
    svc, client, _t, crpc, srpc, cf = make_stack()
    monitor = FusionMonitor(cf).attach_rpc_hub(srpc)
    try:
        assert await client.get("m") == 0
        node = await capture(lambda: client.get("m"))
        await svc.increment("m")
        await asyncio.wait_for(node.when_invalidated(), 5.0)
        report = monitor.report()
        assert "fanout" in report
        assert report["fanout"]["batch_frames_sent"] >= 1
        assert report["fanout"]["invalidations_posted"] >= 1
    finally:
        monitor.dispose()
        await _stop(crpc, srpc)


async def test_wire_codec_transport_roundtrips():
    """The codec-faithful transport (every frame dumps/loads both ways)
    serves calls and invalidation pushes identically."""
    svc, client, _t, crpc, srpc, cf = make_stack(wire_codec=True)
    try:
        assert await client.get("w") == 0
        node = await capture(lambda: client.get("w"))
        await svc.increment("w")
        await asyncio.wait_for(node.when_invalidated(), 5.0)
        assert await client.get("w") == 1
    finally:
        await _stop(crpc, srpc)
