"""``served_dag``: the graph service of ``table_dag`` SERVED, the way the
upstream samples serve theirs (``samples/HelloCart``: Edit command ->
invalidation -> watching clients; ``samples/MultiServerRpc``: the service
behind an RPC hub).

Below the service API it is ``table_dag`` step for step (same generator,
same columnar build, same mirrors, ``enable_nonblocking`` and
``enable_super_rounds``, watchdog; the same ``bench:`` span names, so
``graph_build_s`` reads this deployment too). On top of it:

- the service has a command, ``Bump(row, delta)``: a non-idempotent
  increment of the row's stored value; its invalidation replay touches the
  row's compute method (``chip_smoke.py``'s write leg);
- an ``InMemoryOperationLog`` attached to the commander's operations
  pipeline, and a one-member ``ClusterCommander`` over it: commands are
  journaled before completion, completion's invalidation is submitted to
  the wave pipeline;
- an ``RpcHub`` server with the compute call type and the fan-out index
  (``install_compute_fanout``); ``clients`` client hubs, each over its own
  ``RpcTestTransport(wire_codec=True)`` (every frame pays serialization
  both ways), each with a compute-client proxy of the service;
- the round driver: a task that calls ``ClusterCommander.drain()`` every
  ``drain_tick_ms``, on a fixed-rate timer (``perf/write_path.py``'s
  drainer has the same cadence). Commands accumulate as pending waves
  between ticks; a tick dispatches them, applies them and so feeds the
  fan-out. ``rpc/outbox.py`` has no timer of its own: a peer's
  drain task runs as soon as the event loop reaches it after a post;
- the key pool the traffic writes to, fixed by the configuration
  (``pool_*`` sizes): rows of the upper half of ids with out-degree >= 2
  whose closures hold ``pool_closure_min``..``pool_closure_max`` rows and
  are pairwise disjoint, and for each its first direct dependent.

Who subscribes to what is the traffic's (dealt from ``--seed``).
"""
import asyncio
import threading
import time

import numpy as np

from deployments import table_dag


class Deployment(table_dag.Deployment):
    def __init__(self):
        super().__init__()
        self.log_store = self.reader = None
        self.commander = None  # ClusterCommander
        self.Bump = None
        self.server_rpc = None
        self.clients: list = []  # Client
        self.pool_rows = self.pool_deps = None  # int64[pool]
        self.pool_closures: list = []  # closure size of each pool row
        #: (start, end, newly) of every drain tick that had a wave to
        #: dispatch, perf_counter seconds
        self.drains: list = []
        self._drainer = None
        self._stop = False

    def _outboxes(self):
        return [p._outbox for p in self.server_rpc.peers.values() if p._outbox is not None]

    def fallbacks(self) -> dict:
        out = super().fallbacks()
        out["outbox_drain_faults"] = sum(ob.drain_faults for ob in self._outboxes())
        return out

    def outbox_totals(self) -> dict:
        boxes = self._outboxes()
        return {
            "batch_frames": sum(ob.batch_frames_sent for ob in boxes),
            "batch_keys": sum(ob.batch_keys_sent for ob in boxes),
        }

    async def _drain_loop(self, tick_s: float) -> None:
        """The round driver: a fixed-rate timer on the loop's clock. Ticks
        fall on multiples of ``tick_s``; one that a long drain overran is
        skipped, not queued. (Sleeping ``tick_s`` after each drain instead
        ties every tick's phase to the previous command's drain: with one
        writer in a closed loop the next command then arrives right beside
        a tick boundary, and the median moved 3.5 % from seed to seed
        against 0.8 % on the timer; PERF.md, PR 28.)"""
        pipe, commander, drains = self.pipe, self.commander, self.drains
        loop = asyncio.get_running_loop()
        due = loop.time()
        while not self._stop:
            pending = pipe.stats()["pending_waves"]
            t0 = time.perf_counter()
            newly = commander.drain()
            if pending:
                drains.append((t0, time.perf_counter(), int(newly)))
            now = loop.time()
            due += tick_s * (int((now - due) / tick_s) + 1)
            await asyncio.sleep(due - now)


class Client:
    """One subscribed client: its own fusion hub and RPC hub over a
    codec-faithful in-memory link, and a proxy of the served service."""

    def __init__(self, i: int, server_rpc):
        from stl_fusion_tpu.client import compute_client, install_compute_call_type
        from stl_fusion_tpu.core import FusionHub
        from stl_fusion_tpu.rpc import RpcHub, RpcTestTransport

        self.i = i
        self.rpc = RpcHub(f"client-{i}")
        install_compute_call_type(self.rpc)
        self.transport = RpcTestTransport(self.rpc, server_rpc, wire_codec=True)
        self.proxy = compute_client("dag", self.rpc, FusionHub(), peer_ref=f"c{i}")

    async def read(self, row: int):
        """Read (or re-read) a row: the value and the client's computed,
        whose invalidation is the subscription's signal."""
        from stl_fusion_tpu.core import capture

        computed = await capture(lambda: self.proxy.node(row))
        return computed.value, computed


def make_served_service(n: int):
    """``table_dag``'s service plus the write: ``chip_smoke.py``'s ``Bump``."""
    import dataclasses

    from stl_fusion_tpu.commands import command_handler
    from stl_fusion_tpu.core import is_invalidating
    from stl_fusion_tpu.utils.serialization import wire_type

    @wire_type("BenchServedBump")
    @dataclasses.dataclass(frozen=True)
    class Bump:
        row: int
        delta: float

        def shard_key(self):
            return f"row-{self.row}"

    class ServedDag(table_dag.make_service(n)):
        @command_handler
        async def bump(self, command: Bump):
            if is_invalidating():
                await self.node(command.row)
                return
            self.base[command.row] += np.float32(command.delta)
            self._base_dev = None  # the device loader's copy is re-made when next used
            return float(self.base[command.row])

    return ServedDag, Bump


def choose_pool(oracle, n: int, src, size: int, seed: int, lo: int, hi: int):
    """``size`` rows of the upper half of ids with out-degree >= 2 whose
    closures hold ``lo``..``hi`` rows and share no row with one another,
    taken in a shuffled order fixed by ``seed``; with each its first direct
    dependent and its closure's size."""
    outdeg = np.bincount(src, minlength=n)
    candidates = np.flatnonzero(outdeg[n // 2:] >= 2) + n // 2
    order = np.random.default_rng([seed, 0x9001]).permutation(len(candidates))
    rows, deps, sizes, taken = [], [], [], set()
    for row in candidates[order].tolist():
        closure = oracle.closure_ids([row])
        if not lo <= len(closure) <= hi or not taken.isdisjoint(closure):
            continue
        taken |= closure
        rows.append(row)
        deps.append(int(oracle.out_neighbors([row])[0]))
        sizes.append(len(closure))
        if len(rows) == size:
            break
    if len(rows) < size:
        raise RuntimeError(f"only {len(rows)} of {size} pool rows found")
    return np.asarray(rows, np.int64), np.asarray(deps, np.int64), sizes


async def build(ctx) -> Deployment:
    from lib.hostgraph import HostGraph, power_law_dag
    from lib.result import note
    from stl_fusion_tpu.client import install_compute_call_type
    from stl_fusion_tpu.commands import ClusterCommander
    from stl_fusion_tpu.core import FusionHub, memo_table_of, set_default_hub
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.oplog import (
        InMemoryOperationLog,
        LocalChangeNotifier,
        attach_operation_log,
    )
    from stl_fusion_tpu.resilience import WaveWatchdog
    from stl_fusion_tpu.rpc import RpcHub, install_compute_fanout

    from stl_fusion_tpu.graph.device_graph import DeviceGraph

    if not hasattr(DeviceGraph, "lat_serves"):
        # a program whose pipeline sends every command wave through a whole
        # topo sweep (0.8 s a command at this size) cannot run this
        # deployment: say so at once, before anything is built
        note("this checkout's wave pipeline has no small-wave routing "
             "(DeviceGraph.lat_serves): plawdag-served-1c cannot run on it")
        raise SystemExit(3)
    m = ctx.m
    dep = Deployment()
    n = dep.n = int(ctx.size("nodes"))
    row_words = int(ctx.size("row_words"))
    graph_seed = int(ctx.size("graph_seed"))
    note(f"generating the {n:,}-node power-law DAG (graph seed {graph_seed})")
    with m.span("graph_generate"):
        src, dst = power_law_dag(
            n, avg_degree=ctx.size("avg_degree"), seed=graph_seed,
            alpha=ctx.size("alpha"),
        )
        dep.oracle = HostGraph(src, dst, n)
    dep.hub = FusionHub()
    dep.old_hub = set_default_hub(dep.hub)
    dep.backend = TpuGraphBackend(
        dep.hub,
        node_capacity=n + 64,
        edge_capacity=len(src) + int(ctx.size("edge_headroom")),
    )
    dep.watchdog = dep.backend.attach_watchdog(
        WaveWatchdog(deadline_s=float(ctx.size("watchdog_deadline_s")))
    )
    service, dep.Bump = make_served_service(n)
    dep.svc = service(dep.hub)
    dep.hub.add_service(dep.svc, "dag")
    dep.hub.commander.add_service(dep.svc)
    dep.log_store = InMemoryOperationLog()
    dep.reader = attach_operation_log(
        dep.hub.commander, dep.log_store, LocalChangeNotifier()
    )
    dep.table = memo_table_of(dep.svc.node)
    note("columnar build (bind_table_rows, declare_row_edges, device warm)")
    with m.span("columnar_build"):
        dep.block = dep.backend.bind_table_rows(dep.table)
        dep.backend.declare_row_edges(dep.block, src, dep.block, dst)
        dep.backend.warm_block_on_device(dep.block)
        dep.backend.flush()
    if dep.backend.node_count != n or dep.table.stale_count() != 0:
        raise RuntimeError("the built graph is not the declared one")
    dep.gdev = dep.backend.graph
    note("building the topo and lat mirrors")
    with m.span("mirror_build"):
        mirror = dep.gdev.build_topo_mirror()
        for thread in threading.enumerate():  # as table_dag: no shared window
            if thread.name == "mirror-cache-save":
                thread.join()
    m.values["mirror_levels"] = mirror["levels"]
    dep.pipe = dep.hub.enable_nonblocking(
        fuse_depth=int(ctx.size("fuse_depth")), max_words=row_words
    )
    dep.sr = dep.backend.enable_super_rounds(
        dep.block, depth=int(ctx.size("super_round_depth")), max_words=row_words
    )
    m.values["graph_build_s"] = (
        m.span_seconds("graph_generate") + m.span_seconds("columnar_build")
        + m.span_seconds("mirror_build")
    )
    m.values["edges"] = int(len(src))

    with m.span("pool"):
        dep.pool_rows, dep.pool_deps, dep.pool_closures = choose_pool(
            dep.oracle, n, src, int(ctx.size("pool_rows")),
            int(ctx.size("pool_seed")), int(ctx.size("pool_closure_min")),
            int(ctx.size("pool_closure_max")),
        )
    note(f"key pool: {len(dep.pool_rows)} rows, closures "
         f"{min(dep.pool_closures)}..{max(dep.pool_closures)} rows")
    dep.commander = ClusterCommander(
        dep.hub.commander, member_id="m0", log_store=dep.log_store
    )
    dep.server_rpc = RpcHub("server")
    install_compute_call_type(dep.server_rpc)
    dep.server_rpc.add_service("dag", dep.svc)
    install_compute_fanout(dep.server_rpc, dep.backend)
    with m.span("clients"):
        dep.clients = [
            Client(i, dep.server_rpc) for i in range(int(ctx.size("clients")))
        ]
    dep._drainer = asyncio.get_running_loop().create_task(
        dep._drain_loop(float(ctx.size("drain_tick_ms")) / 1e3)
    )
    return dep


async def close(dep: Deployment) -> None:
    dep._stop = True
    try:
        if dep._drainer is not None:
            await dep._drainer
        for client in dep.clients:
            await client.rpc.stop()
        if dep.server_rpc is not None:
            await dep.server_rpc.stop()
        if dep.reader is not None:
            await dep.reader.stop()
    finally:
        await table_dag.close(dep)
