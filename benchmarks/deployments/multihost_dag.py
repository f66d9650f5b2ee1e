"""``multihost_dag``: several ``served_dag`` members side by side, as the
upstream multi-host sample runs its hosts (``samples/Run-TodoApp-MultiHost.cmd``:
hosts on one database, each host's ``DbOperationLogReader`` replaying the
others' operations as invalidations), with commands routed to their key's
owner as ``samples/MultiServerRpc`` routes calls.

One process, one event loop, ``members`` members; member ``k`` lives on
``jax.devices()[k]``. Each member is ``served_dag``'s member step for step
(its service class, key pool and ``Client`` are imported from there, not
copied): its own ``FusionHub``, its own ``TpuGraphBackend(device=...)``
holding the WHOLE graph (replicas, as the source's hosts are: every host can
answer every key), both mirrors, ``enable_nonblocking``,
``enable_super_rounds``, a watchdog, its own ``RpcHub`` with the fan-out
index, its own clients over ``RpcTestTransport(wire_codec=True)`` and its own
round driver (``ClusterCommander.drain()`` on the fixed-rate timer).

What the members share is what the sample's hosts share:

- ONE store, the service's base array (the sample's database): every
  member's service reads it, the owner's handler writes it;
- ONE ``SqliteOperationLog`` (WAL, ``synchronous`` passed as an argument) in
  a directory the run makes and removes, with one ``LocalChangeNotifier``;
  each member's operations pipeline appends to it and each member's reader
  tails it (``attach_operation_log``).

Commands enter through a pure client: a ``ClusterCommander`` whose
``member_id`` no map owns, a ``ShardMapRouter`` over the static
``ShardMap.initial([m0..], shards)`` and one
``RpcMultiServerTestTransport(wire_codec=True)`` to the members, each of
which exposes its commander (``expose_cluster_commander``) and holds the
same static map, so the owner-side re-check bounces a command that reaches
a member that does not own its key. No heartbeat membership.

On a checkout whose ``TpuGraphBackend`` cannot be told its device the
deployment says so and exits 3 before it generates anything.
"""
import asyncio
import inspect
import os
import shutil
import sys
import threading

import numpy as np

from deployments import routed_dag, served_dag, table_dag

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
LOG_ROOT = os.path.join(ROOT, ".bench_oplog")


class Member(served_dag.Deployment):
    """One served member: ``served_dag``'s deployment, on a device of its
    own. Round driver, fallbacks and outbox totals are the parent's."""

    def __init__(self, name: str, device):
        super().__init__()
        self.name = name
        self.device = device
        self.agent_id = None  # this member's operations agent, as journaled

    def layout_misplaced(self) -> list:
        """Resident arrays of this member that are not committed to its
        device and to it alone."""
        want = [self.device.id]
        return sorted(
            name for name, where in self.backend.device_layout().items()
            if where["devices"] != want or not where["committed"]
        )


class Deployment:
    def __init__(self):
        self.n = 0
        self.oracle = None  # lib.hostgraph.HostGraph
        self.members: list = []  # Member
        self.store = None  # float32[n]: the one base array
        self.log_store = None
        self.log_dir = None
        self.notifier = None
        self.shards = 0
        self.Bump = None
        self.writer = None  # ClusterCommander, a pure client
        self.writer_rpc = None
        self.router = None  # the writer's ShardMapRouter
        self.pool_rows = self.pool_deps = None
        self.pool_closures: list = []
        self.build_s: dict = {}  # the set-up's spans, for the result's notes
        self._metrics0: dict = {}

    metric = staticmethod(routed_dag.Deployment.metric)  # a process-wide counter

    #: process-wide counters of the routed hop; none may move in a run
    ROUTING_FAULTS = (
        "fusion_cmd_retries_total", "fusion_cmd_errors_total",
        "fusion_cmd_dedup_total",
    )

    def fallbacks(self) -> dict:
        """Every counted fallback of every member (``served_dag``'s, by
        member), the routed hop's retries, errors, dedups and bounces since
        the build, and the readers' lane bursts; any nonzero makes the run
        incorrect."""
        out = {}
        for member in self.members:
            for key, value in member.fallbacks().items():
                out[f"{member.name}.{key}"] = value
            out[f"{member.name}.reader_lane_bursts"] = member.reader.replay_lane_bursts
            out[f"{member.name}.reader_corrupt_or_gaps"] = (
                member.reader.corrupt_seen + member.reader.gaps_seen
            )
        for name in self.ROUTING_FAULTS:
            out[name] = self.metric(name) - self._metrics0.get(name, 0)
        # a member that bounced a command it does not own (the RPC hub heals
        # and retries once by itself, so no command counter moves)
        out["writer.moved_rejections"] = self.router.moved_rejections_seen
        return out

    def fallbacks_compared(self):
        fired = {k: v for k, v in self.fallbacks().items() if v}
        return fired, {"name": "fallbacks_fired", "value": sum(fired.values()), "limit": 0}


async def _build_member(ctx, dep, k: int, device, src, dst, smap) -> Member:
    from lib.result import note
    from stl_fusion_tpu.client import install_compute_call_type
    from stl_fusion_tpu.cluster import ShardMapRouter
    from stl_fusion_tpu.commands import ClusterCommander, expose_cluster_commander
    from stl_fusion_tpu.core import FusionHub, memo_table_of
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.oplog import attach_operation_log
    from stl_fusion_tpu.resilience import WaveWatchdog
    from stl_fusion_tpu.rpc import RpcHub, install_compute_fanout

    m, n = ctx.m, dep.n
    row_words = int(ctx.size("row_words"))
    mem = Member(f"m{k}", device)
    mem.hub = FusionHub()
    mem.backend = TpuGraphBackend(
        mem.hub, node_capacity=n + 64,
        edge_capacity=len(src) + int(ctx.size("edge_headroom")),
        device=device,
    )
    mem.watchdog = mem.backend.attach_watchdog(
        WaveWatchdog(deadline_s=float(ctx.size("watchdog_deadline_s")))
    )
    mem.svc = dep.service(mem.hub)
    mem.svc.base = dep.store  # one store: the sample's one database
    mem.hub.add_service(mem.svc, "dag")
    mem.hub.commander.add_service(mem.svc)
    mem.log_store = dep.log_store
    mem.reader = attach_operation_log(mem.hub.commander, dep.log_store, dep.notifier)
    mem.agent_id = mem.hub.commander.operations.agent.id
    mem.table = memo_table_of(mem.svc.node)
    note(f"{mem.name} on {device}: columnar build, mirrors")
    with m.span("columnar_build"):
        mem.block = mem.backend.bind_table_rows(mem.table)
        mem.backend.declare_row_edges(mem.block, src, mem.block, dst)
        mem.backend.warm_block_on_device(mem.block)
        mem.backend.flush()
    if mem.backend.node_count != n or mem.table.stale_count() != 0:
        raise RuntimeError(f"{mem.name}: the built graph is not the declared one")
    mem.gdev = mem.backend.graph
    with m.span("mirror_build"):
        mirror = mem.gdev.build_topo_mirror()
        # the first member of a checkout's first run writes the mirror disk
        # cache from a background thread: wait, so that the next member's
        # build finds it and no window shares the host with that write
        for thread in threading.enumerate():
            if thread.name == "mirror-cache-save":
                thread.join()
    m.values["mirror_levels"] = mirror["levels"]
    mem.pipe = mem.hub.enable_nonblocking(
        fuse_depth=int(ctx.size("fuse_depth")), max_words=row_words
    )
    mem.sr = mem.backend.enable_super_rounds(
        mem.block, depth=int(ctx.size("super_round_depth")), max_words=row_words
    )
    mem.pool_rows, mem.pool_deps = dep.pool_rows, dep.pool_deps
    mem.server_rpc = RpcHub(mem.name)
    install_compute_call_type(mem.server_rpc)
    mem.server_rpc.add_service("dag", mem.svc)
    install_compute_fanout(mem.server_rpc, mem.backend)
    mem.commander = ClusterCommander(
        mem.hub.commander, member_id=mem.name, log_store=dep.log_store,
        router=ShardMapRouter(mem.server_rpc, shard_map=smap),
    )
    expose_cluster_commander(mem.server_rpc, mem.commander)
    with m.span("clients"):
        mem.clients = [
            served_dag.Client(i, mem.server_rpc)
            for i in range(int(ctx.size("clients")))
        ]
    return mem


async def build(ctx) -> Deployment:
    import jax

    from lib.hostgraph import HostGraph, power_law_dag
    from lib.result import note
    from stl_fusion_tpu.cluster import ShardMap, ShardMapRouter
    from stl_fusion_tpu.commands import ClusterCommander
    from stl_fusion_tpu.core import FusionHub
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.oplog import LocalChangeNotifier, SqliteOperationLog
    from stl_fusion_tpu.rpc import RpcHub
    from stl_fusion_tpu.rpc.testing import RpcMultiServerTestTransport

    if "device" not in inspect.signature(TpuGraphBackend.__init__).parameters:
        # a program that cannot place a backend would put every member's
        # graph on chip 0: say so at once, before anything is built
        note("this checkout's TpuGraphBackend takes no device: "
             "plawdag-mh-4c (a member a chip) cannot run on it")
        raise SystemExit(3)
    members = int(ctx.size("members"))
    if len(jax.devices()) < members:
        print(f"# benchmark: {ctx.cell['name']} needs {members} devices, JAX shows "
              f"{len(jax.devices())}; a CPU rehearsal gets them with "
              f"XLA_FLAGS=--xla_force_host_platform_device_count={members}",
              file=sys.stderr)
        raise SystemExit(2)
    m = ctx.m
    dep = Deployment()
    n = dep.n = int(ctx.size("nodes"))
    graph_seed = int(ctx.size("graph_seed"))
    note(f"generating the {n:,}-node power-law DAG (graph seed {graph_seed})")
    with m.span("graph_generate"):
        src, dst = power_law_dag(
            n, avg_degree=ctx.size("avg_degree"), seed=graph_seed,
            alpha=ctx.size("alpha"),
        )
        dep.oracle = HostGraph(src, dst, n)
    with m.span("pool"):
        dep.pool_rows, dep.pool_deps, dep.pool_closures = served_dag.choose_pool(
            dep.oracle, n, src, int(ctx.size("pool_rows")),
            int(ctx.size("pool_seed")), int(ctx.size("pool_closure_min")),
            int(ctx.size("pool_closure_max")),
        )
    note(f"key pool: {len(dep.pool_rows)} rows, closures "
         f"{min(dep.pool_closures)}..{max(dep.pool_closures)} rows")
    dep.service, dep.Bump = served_dag.make_served_service(n)
    dep.store = np.arange(n, dtype=np.float32)
    dep.log_dir = os.path.join(LOG_ROOT, f"{ctx.cell['name']}-{ctx.seed}-{os.getpid()}")
    shutil.rmtree(dep.log_dir, ignore_errors=True)
    os.makedirs(dep.log_dir)
    dep.log_store = SqliteOperationLog(
        os.path.join(dep.log_dir, "operations.sqlite"),
        synchronous=str(ctx.size("oplog_synchronous")),
    )
    dep.notifier = LocalChangeNotifier()
    dep.shards = int(ctx.size("shards"))
    names = [f"m{k}" for k in range(members)]
    smap = ShardMap.initial(names, n_shards=dep.shards)
    for k in range(members):
        dep.members.append(
            await _build_member(ctx, dep, k, jax.devices()[k], src, dst, smap)
        )
    m.values["graph_build_s"] = (
        m.span_seconds("graph_generate") + m.span_seconds("columnar_build")
        + m.span_seconds("mirror_build")
    )
    m.values["edges"] = int(len(src))
    dep.build_s = {
        name: m.span_seconds(name) for name in
        ("graph_generate", "pool", "columnar_build", "mirror_build", "clients")
    }

    dep.writer_rpc = RpcHub("writer")
    RpcMultiServerTestTransport(
        dep.writer_rpc, {mem.name: mem.server_rpc for mem in dep.members},
        wire_codec=True, client_name="w0",
    )
    dep.router = ShardMapRouter(dep.writer_rpc, shard_map=smap)
    dep.writer_rpc.call_router = dep.router
    dep.writer = ClusterCommander(
        FusionHub().commander, router=dep.router, member_id="w0",
        rpc_hub=dep.writer_rpc,
        call_timeout_s=float(ctx.size("forward_timeout_s")),
    )
    dep._metrics0 = {name: dep.metric(name) for name in dep.ROUTING_FAULTS}
    loop = asyncio.get_running_loop()
    tick_s = float(ctx.size("drain_tick_ms")) / 1e3
    for mem in dep.members:
        mem._drainer = loop.create_task(mem._drain_loop(tick_s))
    return dep


async def close(dep: Deployment) -> None:
    try:
        for mem in dep.members:
            mem._stop = True
        for mem in dep.members:
            if mem._drainer is not None:
                await mem._drainer
        if dep.writer_rpc is not None:
            await dep.writer_rpc.stop()
        for mem in dep.members:
            for client in mem.clients:
                await client.rpc.stop()
            if mem.server_rpc is not None:
                await mem.server_rpc.stop()
            if mem.reader is not None:
                await mem.reader.stop()
    finally:
        for mem in dep.members:
            await table_dag.close(mem)
        if dep.log_store is not None:
            dep.log_store.close()
        if dep.log_dir is not None:
            shutil.rmtree(dep.log_dir, ignore_errors=True)
