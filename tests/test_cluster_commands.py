"""Cluster command plane tests (ISSUE 20 tentpole): routed writes via the
ClusterCommander — duplicate-op-id replays dedup against the memo AND the
shared journal, a no-longer-owner bounces a mid-flight command instead of
double-applying, a killed owner's replay lands exactly once on the survivor
after counted bounded backoff, a cross-host command rides the real
``rpc/tcp.py`` DCN socket, command-minted waves fuse into the nonblocking
pipeline with ``explain()`` naming the originating command end to end, the
oplog's cause column round-trips (including the pre-ISSUE-20 sqlite schema
migration), and the rpc_bridge heals the router's map before a
``ShardMovedError`` surfaces."""
import dataclasses
import sqlite3

import numpy as np
import pytest

from test_cluster import Cluster

from stl_fusion_tpu.client import install_compute_call_type
from stl_fusion_tpu.cluster import ShardMap, ShardMapRouter, ShardMovedError
from stl_fusion_tpu.commands import (
    ClusterCommander,
    bridge_commands,
    command_handler,
    expose_cluster_commander,
)
from stl_fusion_tpu.core import (
    ComputeService,
    FusionHub,
    TableBacking,
    capture,
    compute_method,
    is_invalidating,
    memo_table_of,
)
from stl_fusion_tpu.diagnostics import explain, global_metrics
from stl_fusion_tpu.diagnostics.mesh_telemetry import global_mesh_trace
from stl_fusion_tpu.graph import TpuGraphBackend
from stl_fusion_tpu.oplog import (
    InMemoryOperationLog,
    LocalChangeNotifier,
    attach_operation_log,
)
from stl_fusion_tpu.oplog.log import OperationRecord, SqliteOperationLog
from stl_fusion_tpu.rpc import RpcHub, RpcTestTransport
from stl_fusion_tpu.rpc.tcp import RpcTcpServer, tcp_client_connector
from stl_fusion_tpu.utils.serialization import wire_type


# ------------------------------------------------------------------ harness

@wire_type("CartAdd")
@dataclasses.dataclass(frozen=True)
class CartAdd:
    """A NON-idempotent write (increment): a double-apply or a lost write
    is directly observable against the shared-store oracle."""

    cart: str
    qty: int

    def shard_key(self) -> str:
        return self.cart


class CartSvc(ComputeService):
    def __init__(self, hub, store):
        super().__init__(hub)
        self.store = store

    @compute_method
    async def total(self, cart: str) -> int:
        return self.store.get(cart, 0)

    @command_handler
    async def add(self, command: CartAdd):
        if is_invalidating():
            await self.total(command.cart)
            return
        self.store[command.cart] = self.store.get(command.cart, 0) + command.qty
        return self.store[command.cart]


class CommandCluster(Cluster):
    """The test_cluster harness plus a ClusterCommander per member (owning
    the shared journal) and one on the routed client (member id no map will
    ever own, so every call forwards through the router)."""

    def __init__(self, refs, **kw):
        self.cart_store = {}
        self.commanders = {}
        kw.setdefault("oplog", True)
        super().__init__(refs, **kw)
        self.client_commander = ClusterCommander(
            commander=self.client_fusion.commander,
            router=self.router,
            member_id="c0",
            rpc_hub=self.client_rpc,
            max_retries=20,
        )

    def _build_server(self, ref, attach_reader=True):
        super()._build_server(ref, attach_reader)
        cart = CartSvc(self.fusions[ref], self.cart_store)
        self.hubs[ref].add_service("cart", cart)
        self.fusions[ref].commander.add_service(cart)
        cc = ClusterCommander(
            commander=self.fusions[ref].commander,
            member_id=ref,
            rpc_hub=self.hubs[ref],
            log_store=self.log_store,
        )
        self.commanders[ref] = cc
        expose_cluster_commander(self.hubs[ref], cc)

    def _wire_server(self, ref, seeds):
        super()._wire_server(ref, seeds)
        # the member's OWN map is the ownership truth for the pre-apply
        # re-check (the router on the client can be staler than the mesh)
        self.commanders[ref].member = self.members[ref]

    async def wait_bootstrap(self):
        await self.wait_epoch(
            lambda: all(m.shard_map.epoch >= 1 for m in self.members.values()),
            what="bootstrap epoch",
        )


def _cart_key(command: CartAdd) -> str:
    return repr(command.shard_key())


# ------------------------------------------------------------------ dedup

async def test_routed_command_applies_once_and_duplicate_op_id_dedups():
    c = CommandCluster(["m0", "m1", "m2"])
    try:
        await c.wait_bootstrap()
        dedup = global_metrics().counter("fusion_cmd_dedup_total")
        before = dedup.value
        op = "op-dup-check-000000000000"
        assert await c.client_commander.call(CartAdd("cart-a", 3), operation_id=op) == 3
        assert c.cart_store["cart-a"] == 3
        # the duplicate send (same idempotency token) is absorbed: the
        # FIRST application's result comes back, the store is untouched
        assert await c.client_commander.call(CartAdd("cart-a", 3), operation_id=op) == 3
        assert c.cart_store["cart-a"] == 3
        assert dedup.value == before + 1
        # a fresh operation id applies on top
        assert await c.client_commander.call(CartAdd("cart-a", 2)) == 5
        assert c.cart_store["cart-a"] == 5
    finally:
        await c.stop()


# ------------------------------------------------------------------ reshard

async def test_non_owner_bounces_mid_flight_command_instead_of_double_applying():
    """The mid-command reshard contract: a member that is NOT the owner of
    a command's shard (the map moved while the envelope was in flight)
    bounces with ShardMovedError carrying its map — the command is NOT
    applied there; the retry under the SAME op id applies exactly once on
    the real owner, and a later re-delivery dedups."""
    c = CommandCluster(["m0", "m1", "m2"])
    try:
        await c.wait_bootstrap()
        # a cart whose shard m0 does NOT own: delivering it to m0 models
        # the stale-map mid-flight arrival
        cmd = next(
            CartAdd(f"cart-{i}", 1)
            for i in range(64)
            if c.members["m0"].shard_map.owner_of(_cart_key(CartAdd(f"cart-{i}", 1))) != "m0"
        )
        owner = c.members["m0"].shard_map.owner_of(_cart_key(cmd))
        op = "op-moved-111111111111"
        with pytest.raises(ShardMovedError) as ei:
            await c.commanders["m0"].execute_local(cmd, op)
        assert ei.value.shard_map is not None  # the healing map rides the bounce
        assert cmd.cart not in c.cart_store  # NOT applied by the non-owner
        # the client retry with the same op id: exactly one application
        assert await c.client_commander.call(cmd, operation_id=op) == 1
        assert c.cart_store[cmd.cart] == 1
        # re-delivery to the owner dedups against memo + shared journal
        assert await c.commanders[owner].execute_local(cmd, op) == 1
        assert c.cart_store[cmd.cart] == 1
    finally:
        await c.stop()


# ------------------------------------------------------------------ host kill

async def test_killed_owner_retries_and_applies_exactly_once_on_survivor():
    c = CommandCluster(["m0", "m1", "m2"])
    try:
        await c.wait_bootstrap()
        cmd = CartAdd("cart-kill", 5)
        victim = c.router.shard_map.owner_of(_cart_key(cmd))
        await c.kill(victim)
        retries = global_metrics().counter("fusion_cmd_retries_total")
        before = retries.value
        op = "op-kill-222222222222"
        # counted bounded backoff rides out the failure-detection window;
        # the write lands exactly once on the survivor that now owns it
        assert await c.client_commander.call(cmd, operation_id=op) == 5
        assert c.cart_store["cart-kill"] == 5
        assert retries.value > before
        new_owner = c.router.shard_map.owner_of(_cart_key(cmd))
        assert new_owner != victim
        # the replay after failover is oracle-exact: dedup, not double-apply
        assert await c.client_commander.call(cmd, operation_id=op) == 5
        assert c.cart_store["cart-kill"] == 5
    finally:
        await c.stop()


# ------------------------------------------------------------------ DCN leg

async def test_cross_host_command_rides_the_real_tcp_dcn_leg():
    """A cross-host owner reached over the exercised rpc/tcp.py socket: the
    enveloped command (operation id and all) crosses a REAL TCP connection,
    applies once, journals, and the duplicate send dedups server-side."""
    store = {}
    log = InMemoryOperationLog()
    server_fusion = FusionHub()
    cart = CartSvc(server_fusion, store)
    server_fusion.commander.add_service(cart)
    reader = attach_operation_log(
        server_fusion.commander, log, LocalChangeNotifier()
    )
    server_rpc = RpcHub("tcp-owner")
    install_compute_call_type(server_rpc)
    server_cc = ClusterCommander(
        server_fusion.commander, member_id="default", log_store=log
    )
    expose_cluster_commander(server_rpc, server_cc)
    server = await RpcTcpServer(server_rpc).start()

    client_rpc = RpcHub("tcp-writer")
    install_compute_call_type(client_rpc)
    client_rpc.client_connector = tcp_client_connector(server.host, server.port)
    # a one-member map whose only owner is the TCP peer ref: every command
    # forwards over the socket (pinned-peer path, no call_router)
    router = ShardMapRouter(client_rpc, members=["default"], n_shards=16)
    client_cc = ClusterCommander(
        FusionHub().commander, router=router, member_id="tcp-writer",
        rpc_hub=client_rpc,
    )
    try:
        forwarded = global_metrics().counter("fusion_cmd_forwarded_total")
        dedup = global_metrics().counter("fusion_cmd_dedup_total")
        f0, d0 = forwarded.value, dedup.value
        op = "op-tcp-333333333333"
        assert await client_cc.call(CartAdd("sock-cart", 2), operation_id=op) == 2
        assert store["sock-cart"] == 2
        assert log.contains(op)  # journaled before the reply crossed back
        # duplicate over the socket: absorbed on the owner
        assert await client_cc.call(CartAdd("sock-cart", 2), operation_id=op) == 2
        assert store["sock-cart"] == 2
        assert forwarded.value == f0 + 2
        assert dedup.value == d0 + 1
    finally:
        await reader.stop()
        await client_rpc.stop()
        await server.stop()


# ------------------------------------------------------------------ waves

ROWS = 16


@wire_type("BumpRow")
@dataclasses.dataclass(frozen=True)
class BumpRow:
    row: int

    def shard_key(self) -> str:
        return f"row-{self.row}"


class ChainSvc(ComputeService):
    """A 16-row chain 0→1→…→15 bound to the device graph: a command on
    row 0 must reach a subscriber of row 5 through the fused wave."""

    def __init__(self, hub=None):
        super().__init__(hub)
        self.base = np.arange(ROWS, dtype=np.float32)

    def load(self, ids):
        return self.base[np.asarray(ids, dtype=np.int64)]

    @compute_method(table=TableBacking(rows=ROWS, batch="load"))
    async def node(self, i: int) -> float:
        return float(self.base[i])

    @command_handler
    async def bump(self, command: BumpRow):
        if is_invalidating():
            await self.node(command.row)
            return
        self.base[command.row] += 1.0
        return float(self.base[command.row])


async def test_command_wave_fuses_into_pipeline_and_explain_names_the_command():
    """The attribution acceptance: a command executed through the
    ClusterCommander completes by submitting its invalidation wave through
    the nonblocking pipeline (zero eager fallbacks), and after the drain
    barrier ``explain()`` on an affected key names the originating command
    ('invalidated by command BumpRow (op …)')."""
    global_mesh_trace().clear()
    hub = FusionHub()
    backend = TpuGraphBackend(hub, node_capacity=ROWS + 8, edge_capacity=64)
    svc = ChainSvc(hub)
    hub.add_service(svc, "chain")
    table = memo_table_of(svc.node)
    block = backend.bind_table_rows(table)
    src = np.arange(ROWS - 1)
    backend.declare_row_edges(block, src, block, src + 1)
    table.read_batch(np.arange(ROWS))
    backend.flush()
    backend.graph.build_topo_mirror()
    hub.commander.add_service(svc)
    hub.commander.attach_operations_pipeline()

    pipe = hub.enable_nonblocking(fuse_depth=8)
    cc = ClusterCommander(hub.commander, member_id="m0")
    # the replay's invalidating touch must find a live computed to seed
    seed_node = await capture(lambda: svc.node(0))
    target = await capture(lambda: svc.node(5))
    target.on_invalidated(lambda c: None)  # eager apply → journal event

    hist = global_metrics().histogram("fusion_cmd_visible_ms", unit="ms")
    ck = hist.checkpoint()
    op = "op-explain-444444444444"
    assert await cc.call(BumpRow(0), operation_id=op) == 1.0
    # nonblocking contract: the command's wave is ACCUMULATED, not applied
    assert pipe.stats()["pending_waves"] == 1
    assert target.is_consistent
    cc.drain()  # the barrier: dispatch + harvest + reconcile tickets
    assert target.is_invalidated
    assert seed_node.is_invalidated
    assert pipe.stats()["eager_waves"] == 0  # no fallback served it
    # one row's wave is a small wave: the lat mirror served it, no chain
    assert pipe.stats()["lat_waves"] == 1
    assert pipe.stats()["fused_dispatches"] == 0

    cause = getattr(target, "invalidation_cause", None) or target._invalidation_cause
    label = global_mesh_trace().command_for(cause)
    assert label is not None and "BumpRow" in label and op[:8] in label, (cause, label)
    report = explain(target, hub=hub)
    assert any(
        "invalidated by command" in line and "BumpRow" in line
        for line in report["chain"]
    ), report["chain"]
    delta = hist.since(ck)
    assert delta["count"] >= 1  # command → client-visible latency recorded
    pipe.dispose()


# ------------------------------------------------------------------ oplog cause

def test_oplog_cause_round_trips_and_legacy_sqlite_schema_migrates(tmp_path):
    cause = "h0/cmd:CartAdd#7"
    rec = OperationRecord("op-x", "agent-1", 123.0, CartAdd("c", 1), (), cause=cause)

    mem = InMemoryOperationLog()
    stored = mem.append(rec)
    assert stored.cause == cause
    assert mem.append(rec).index == stored.index  # id-dedup, never twice
    assert mem.contains("op-x") and not mem.contains("op-y")
    assert mem.read_after(0)[0].cause == cause

    sq = SqliteOperationLog(str(tmp_path / "ops.db"))
    sq.append(rec)
    assert sq.contains("op-x")
    got = sq.read_after(0)[0]
    assert got.cause == cause and got.command == CartAdd("c", 1)
    sq.close()

    # a pre-ISSUE-20 database (no cause_id column) migrates in place: old
    # rows read back with cause=None, new rows carry theirs
    legacy = str(tmp_path / "legacy.db")
    conn = sqlite3.connect(legacy)
    conn.execute(
        """CREATE TABLE operations (
            idx INTEGER PRIMARY KEY AUTOINCREMENT,
            id TEXT UNIQUE, agent_id TEXT, commit_time REAL,
            command_json TEXT, items_json TEXT)"""
    )
    conn.execute(
        "INSERT INTO operations (id, agent_id, commit_time, command_json,"
        " items_json) VALUES ('op-old', 'a0', 1.0, 'null', '[]')"
    )
    conn.commit()
    conn.close()
    sq2 = SqliteOperationLog(legacy)
    sq2.append(rec)
    rows = sq2.read_after(0)
    assert rows[0].id == "op-old" and rows[0].cause is None
    assert rows[1].id == "op-x" and rows[1].cause == cause
    sq2.close()


# ------------------------------------------------------------------ bridge heal

async def test_bridge_applies_carried_map_to_router_before_surfacing():
    """rpc_bridge healing (ISSUE 20 satellite): a bridged command bounced
    by ShardMovedError applies the carried (newer) map to the router BEFORE
    the error surfaces, counted — the caller's retry routes to the new
    owner first try."""
    newer = ShardMap.initial(["a", "b"], n_shards=16, epoch=9)

    class Bouncer:
        async def call(self, command):
            raise ShardMovedError("shard moved", shard_map=newer)

    server_rpc = RpcHub("bounce-server")
    server_rpc.add_service("$commander", Bouncer())
    client_rpc = RpcHub("bounce-client")
    RpcTestTransport(client_rpc, server_rpc)
    router = ShardMapRouter(client_rpc, members=["a"], n_shards=16)
    old_epoch = router.shard_map.epoch
    assert old_epoch < 9

    fusion = FusionHub()
    bridge_commands(fusion.commander, client_rpc, [CartAdd], router=router)
    healed = global_metrics().counter("fusion_cmd_shard_retries_total")
    before = healed.value
    try:
        with pytest.raises(ShardMovedError):
            await fusion.commander.call(CartAdd("x", 1))
        assert router.shard_map.epoch == 9  # healed before surfacing
        assert healed.value == before + 1
    finally:
        await client_rpc.stop()
        await server_rpc.stop()
