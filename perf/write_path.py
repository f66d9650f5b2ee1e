#!/usr/bin/env python
"""Write-path macro-scenario (ISSUE 20): the HelloCart-family counters
workload driven END TO END through the cluster command plane — zipf
writers issue increment commands through the routed ClusterCommander,
every accepted command journals to the shared oplog, completion submits
its invalidation wave through the nonblocking WavePipeline (command waves
FUSE into the resident super-round), and the fences fan out to EdgeNode
sessions. FAILS (nonzero exit) on any SLO violation, so it doubles as a
CI gate:

1. **main burst** — WRITE_WRITERS concurrent zipf writers, WRITE_OPS
   increments total: records write throughput and command→client-visible
   latency percentiles (command issue → the edge session sees a fence
   whose value proves the write landed).
2. **hot-key write storm** — every writer hammers ONE cart: the wave
   pipeline must keep fusing (zero eager fallback rounds), the oracle
   must stay exact (no lost increment under maximal op-id collision
   pressure), and p99 must hold.
3. **write-during-reshard** — a NEW member joins mid-burst: the epoch
   bump moves shards under in-flight commands; movers bounce
   (ShardMovedError), retries land on the new owner, and the oracle is
   exact — never double-applied, never lost.
4. **write-during-host-kill** — a member dies mid-burst: in-flight
   forwards time out, bounded counted backoff rides the failure-detection
   window, replays dedup against the journal, and every write lands
   exactly once on a survivor.
5. **dedup replay** — a sample of already-acked operation ids is
   re-issued verbatim: every replay is absorbed (fusion_cmd_dedup_total
   grows by exactly the sample size, counts unchanged).

Cross-cutting gates: zero lost writes and zero double-applies against
the store oracle (counts[cart] == acked increments, exactly), zero
command errors surfaced to writers, zero eager-fallback waves
attributable to commands, a deliberate fusion probe (pause the drainer,
queue N commands, one drain → a fused dispatch), and the
fusion_cmd_* counters present in the Prometheus exposition.

WRITE_SMOKE=1 (tier1.yml): tiny scale — main burst + storm + owner-kill
+ dedup replay (the reshard join leg is full-run only).

Env: WRITE_SMOKE (0), WRITE_CARTS (2048; smoke 256), WRITE_WRITERS
(32; smoke 4), WRITE_OPS (12_000; smoke 400), WRITE_STORM_OPS (2_000;
smoke 150), WRITE_RESHARD_OPS (1_500), WRITE_KILL_OPS (1_500; smoke
200), WRITE_SESSIONS (2_000; smoke 64), WRITE_MEMBERS (3),
WRITE_SHARDS (64), WRITE_ZIPF (1.1), WRITE_P99_MS (20_000),
WRITE_TIMEOUT_S (600), WRITE_DEDUP_SAMPLE (32; smoke 8).

Prints ONE JSON line (stdout); progress notes go to stderr.
"""
import asyncio
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def note(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


from stl_fusion_tpu.client import install_compute_call_type  # noqa: E402
from stl_fusion_tpu.cluster import (  # noqa: E402
    ClusterMember,
    ShardMap,
    ShardMapRouter,
    install_cluster_client,
    install_cluster_guard,
)
from stl_fusion_tpu.commands import (  # noqa: E402
    ClusterCommander,
    command_handler,
    expose_cluster_commander,
)
from stl_fusion_tpu.core import (  # noqa: E402
    ComputeService,
    FusionHub,
    TableBacking,
    compute_method,
    is_invalidating,
    memo_table_of,
    set_default_hub,
)
from stl_fusion_tpu.diagnostics import global_metrics  # noqa: E402
from stl_fusion_tpu.edge import AdmissionController, EdgeNode  # noqa: E402
from stl_fusion_tpu.graph import TpuGraphBackend  # noqa: E402
from stl_fusion_tpu.oplog import (  # noqa: E402
    InMemoryOperationLog,
    LocalChangeNotifier,
    attach_operation_log,
)
from stl_fusion_tpu.rpc import RpcHub, install_compute_fanout  # noqa: E402
from stl_fusion_tpu.rpc.testing import RpcMultiServerTestTransport  # noqa: E402
from stl_fusion_tpu.utils.serialization import wire_type  # noqa: E402


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"WRITE PATH FAILED: {what}")


async def until(pred, timeout_s: float, what: str) -> None:
    deadline = time.perf_counter() + timeout_s
    while not pred():
        if time.perf_counter() > deadline:
            raise SystemExit(f"WRITE PATH FAILED: timed out waiting for {what}")
        await asyncio.sleep(0.01)


async def settle(seconds: float = 0.05) -> None:
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline:
        await asyncio.sleep(0.005)


class SloGate:
    """Same gate table as perf/traffic_path.py: every check RECORDED,
    pass/fail delegated to ``SloSpec.violated`` (the /health comparator),
    enforce() fails the run on any violation."""

    def __init__(self):
        self.checks = []

    def check(self, name: str, value, ceiling, unit: str = "ms") -> None:
        from stl_fusion_tpu.diagnostics.slo import SloSpec

        spec = SloSpec(name=name, threshold=float(ceiling), comparator="le",
                       unit=unit)
        ok = not spec.violated(value)
        self.checks.append(
            {"name": name, "value": value, "ceiling": ceiling,
             "unit": unit, "ok": ok}
        )
        note(f"SLO {'PASS' if ok else 'FAIL'}: {name} = {value} {unit} "
             f"(ceiling {ceiling})")

    def check_eq(self, name: str, value, want) -> None:
        from stl_fusion_tpu.diagnostics.slo import SloSpec

        spec = SloSpec(name=name, threshold=want, comparator="eq")
        ok = not spec.violated(value)
        self.checks.append(
            {"name": name, "value": value, "ceiling": want, "unit": "eq",
             "ok": ok}
        )
        note(f"SLO {'PASS' if ok else 'FAIL'}: {name} = {value} (want {want})")

    def enforce(self) -> None:
        failed = [c for c in self.checks if not c["ok"]]
        if failed:
            raise SystemExit(
                "WRITE PATH FAILED: SLO violations: "
                + "; ".join(
                    f"{c['name']}={c['value']} (ceiling {c['ceiling']})"
                    for c in failed
                )
            )


def zipf_weights(n: int, a: float) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = 1.0 / ranks**a
    return w / w.sum()


def pctile(values, q: float):
    if not values:
        return None
    arr = np.asarray(values, dtype=np.float64)
    return round(float(np.percentile(arr, q)), 1)


@wire_type("WritePathCartAdd")
@dataclasses.dataclass(frozen=True)
class CartAdd:
    """One order line: a NON-idempotent increment — the only command
    shape under which a double-apply or a lost write is observable."""

    cart: int
    qty: int

    def shard_key(self):
        return f"cart-{self.cart}"


def make_ledger_service(n: int):
    class CartLedger(ComputeService):
        """counts[cart] = orders applied so far. The device table mirrors
        it so command waves are REAL device waves, and the fence re-read
        serves the post-write count — the value the edge audit and the
        visible-latency tracker key on."""

        def __init__(self, hub=None):
            super().__init__(hub)
            self.counts = np.zeros(n, dtype=np.float32)
            self._dev = None

        def load(self, ids):
            return self.counts[np.asarray(ids, dtype=np.int64)]

        def load_dev(self, ids, dev):
            return dev[ids]

        def load_dev_args(self):
            if self._dev is None:
                import jax.numpy as jnp

                self._dev = jnp.asarray(self.counts)
            return (self._dev,)

        @compute_method(
            table=TableBacking(
                rows=n, batch="load",
                device_batch="load_dev", device_args="load_dev_args",
            )
        )
        async def cart(self, i: int) -> float:
            return float(self.counts[i])

        @command_handler
        async def add(self, command: CartAdd):
            if is_invalidating():
                await self.cart(command.cart)
                return
            self.counts[command.cart] += command.qty
            self._dev = None
            return float(self.counts[command.cart])

    return CartLedger


class WriteCluster:
    """The command plane: N heartbeat members (real ClusterMember mesh,
    epoch-stamped guards) all executing against ONE shared FusionHub +
    device graph + journal (the two-hosts-one-DB shape test_cluster.py
    establishes), plus a commands-only routed writer client."""

    def __init__(self, hub, log_store, refs, n_shards, heartbeat=0.05,
                 timeout=0.4):
        self.hub = hub
        self.log_store = log_store
        self.refs = list(refs)
        self.n_shards = n_shards
        self.heartbeat = heartbeat
        self.timeout = timeout
        self.hubs = {}
        self.members = {}
        self.mesh = {}
        self.commanders = {}
        self.killed = set()
        for ref in refs:
            self._build_member(ref)
        for ref in refs:
            self._wire_member(ref, seeds=self.refs)
        self.client_rpc = RpcHub("writer")
        install_compute_call_type(self.client_rpc)
        self.transport = RpcMultiServerTestTransport(
            self.client_rpc, dict(self.hubs), client_name="w0"
        )
        self.router = ShardMapRouter(
            self.client_rpc, members=self.refs, n_shards=n_shards
        )
        self.client_rpc.call_router = self.router
        install_cluster_client(self.client_rpc, self.router)
        self.client_cc = ClusterCommander(
            FusionHub().commander, router=self.router, member_id="w0",
            rpc_hub=self.client_rpc, max_retries=24, call_timeout_s=1.0,
        )

    def _build_member(self, ref):
        rpc = RpcHub(ref)
        install_compute_call_type(rpc)
        self.hubs[ref] = rpc
        cc = ClusterCommander(
            self.hub.commander, member_id=ref, rpc_hub=rpc,
            log_store=self.log_store,
        )
        expose_cluster_commander(rpc, cc)
        self.commanders[ref] = cc

    def _wire_member(self, ref, seeds):
        others = {
            r: h for r, h in self.hubs.items()
            if r != ref and r not in self.killed
        }
        self.mesh[ref] = RpcMultiServerTestTransport(
            self.hubs[ref], others, client_name=ref
        )
        member = ClusterMember(
            self.hubs[ref], ref, seeds=seeds, n_shards=self.n_shards,
            heartbeat_interval=self.heartbeat, failure_timeout=self.timeout,
        ).install()
        install_cluster_guard(self.hubs[ref], member)
        self.members[ref] = member
        self.commanders[ref].member = member

    async def wait_bootstrap(self, timeout_s=10.0):
        await until(
            lambda: all(
                self.members[r].shard_map.epoch >= 1
                for r in self.refs if r not in self.killed
            ),
            timeout_s, "bootstrap epoch",
        )

    async def join(self, ref):
        """Live join mid-traffic: the epoch bump moves shards under
        in-flight commands (the reshard adversarial leg)."""
        self._build_member(ref)
        for r, t in self.mesh.items():
            if r != ref and r not in self.killed:
                t.servers[ref] = self.hubs[ref]
        self.transport.servers[ref] = self.hubs[ref]
        live = [r for r in self.refs if r not in self.killed]
        self._wire_member(ref, seeds=[ref, min(live)])
        self.refs.append(ref)

    async def kill(self, ref):
        """Real member death mid-traffic: unreachable from everyone."""
        self.killed.add(ref)
        for t in list(self.mesh.values()) + [self.transport]:
            t.servers.pop(ref, None)
        await self.members[ref].dispose()
        await self.hubs[ref].stop()

    def live(self):
        return [r for r in self.refs if r not in self.killed]

    def reconcile(self):
        for r, cc in self.commanders.items():
            if r not in self.killed:
                cc.reconcile()

    async def stop(self):
        for r, m in self.members.items():
            if r not in self.killed:
                await m.dispose()
        await self.client_rpc.stop()
        for r, h in self.hubs.items():
            if r not in self.killed:
                await h.stop()


async def main() -> None:
    from stl_fusion_tpu.graph import enable_program_cache, require_accelerator

    device = require_accelerator("perf/write_path.py")
    enable_program_cache()
    smoke = os.environ.get("WRITE_SMOKE", "0") == "1"

    def env_int(name, full, small):
        return int(os.environ.get(name, small if smoke else full))

    n_carts = env_int("WRITE_CARTS", 2048, 256)
    n_writers = env_int("WRITE_WRITERS", 32, 4)
    n_ops = env_int("WRITE_OPS", 12_000, 400)
    storm_ops = env_int("WRITE_STORM_OPS", 2_000, 150)
    reshard_ops = env_int("WRITE_RESHARD_OPS", 1_500, 0)
    kill_ops = env_int("WRITE_KILL_OPS", 1_500, 200)
    n_sessions = env_int("WRITE_SESSIONS", 2_000, 64)
    n_members = int(os.environ.get("WRITE_MEMBERS", 3))
    n_shards = int(os.environ.get("WRITE_SHARDS", 64))
    zipf_a = float(os.environ.get("WRITE_ZIPF", 1.1))
    p99_ceiling = float(os.environ.get("WRITE_P99_MS", 20_000))
    timeout_s = float(os.environ.get("WRITE_TIMEOUT_S", 600))
    dedup_sample_n = env_int("WRITE_DEDUP_SAMPLE", 32, 8)
    rng = np.random.default_rng(2026)
    slo = SloGate()

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        # -- value plane: the cart ledger as a device-mirrored table with
        # shallow pair edges (cart 2k → 2k+1: real cascades, bounded blast)
        backend = TpuGraphBackend(
            hub, node_capacity=n_carts + 64, edge_capacity=n_carts + 1024,
        )
        Ledger = make_ledger_service(n_carts)
        svc = Ledger(hub)
        hub.add_service(svc, "ledger")
        hub.commander.add_service(svc)
        log_store = InMemoryOperationLog()
        reader = attach_operation_log(
            hub.commander, log_store, LocalChangeNotifier()
        )
        table = memo_table_of(svc.cart)
        note("columnar build + device warm...")
        block = backend.bind_table_rows(table)
        even = np.arange(0, n_carts - 1, 2, dtype=np.int64)
        backend.declare_row_edges(block, even, block, even + 1)
        backend.warm_block_on_device(block)
        backend.flush()
        backend.graph.build_topo_mirror()
        pipe = hub.enable_nonblocking(fuse_depth=8)

        # -- the command plane: heartbeat members + routed writer client
        refs = [f"m{i}" for i in range(n_members)]
        note(f"bootstrapping {n_members} command members...")
        cluster = WriteCluster(hub, log_store, refs, n_shards)
        await cluster.wait_bootstrap()

        # -- edge delivery plane: fences fan out of the shared backend
        s0 = RpcHub("s0")
        install_compute_call_type(s0)
        s0.add_service("ledger", svc)
        install_compute_fanout(s0, backend)
        edge_rpc = RpcHub("edge-0")
        install_compute_call_type(edge_rpc)
        RpcMultiServerTestTransport(edge_rpc, {"s0": s0}, client_name="e0")
        edge_router = ShardMapRouter(
            edge_rpc, shard_map=ShardMap.initial(["s0"], epoch=1)
        )
        admission = AdmissionController(
            connect_rate=1e6, connect_burst=1e6, subscribe_rate=1e6,
            subscribe_burst=1e6, name="edge-0",
        )
        edge = EdgeNode(
            "ledger", edge_rpc, router=edge_router, name="edge-0",
            fan_workers=2, reread_batch=True, value_blocks=False,
            admission=admission,
        )

        # -- command→client-visible tracker: the writer appends (post-write
        # count, issue time); the session sink matures every threshold the
        # fence's value proves delivered
        cart_of_key = {}
        visible: dict = {}
        vis_deltas: list = []
        last: dict = {}

        def make_sink(sid):
            def sink(frame):
                last[(sid, frame[0])] = frame
                cart = cart_of_key.get(frame[0])
                if cart is None or frame[5] is not None:
                    return
                v = float(frame[2])
                pending = visible.get(cart)
                if pending:
                    matured = [e for e in pending if e[0] <= v]
                    if matured:
                        now = time.perf_counter()
                        vis_deltas.extend(
                            (now - t0) * 1e3 for _, t0 in matured
                        )
                        visible[cart] = [e for e in pending if e[0] > v]
            return sink

        note(f"attaching {n_sessions} edge sessions (zipf a={zipf_a})...")
        weights = zipf_weights(n_carts, zipf_a)
        picks = rng.choice(n_carts, size=n_sessions, p=weights)
        subscribed = sorted(set(int(c) for c in picks))
        for c in subscribed:
            cart_of_key[edge.key_str(("cart", c))] = c
        for si, c in enumerate(picks):
            edge.attach(
                [("cart", int(c))], sink=make_sink(f"s{si}"),
                replay_current=False, admitted=True,
            )
        await until(
            lambda: all(s.version >= 1 for s in edge._subs.values()),
            timeout_s, "edge upstream warm",
        )

        # -- the harness IS the round driver: a fixed-cadence drain loop
        # (commands fuse between ticks; the probe below proves it)
        drain_on = asyncio.Event()
        drain_on.set()
        stop_drainer = False

        async def drainer():
            while not stop_drainer:
                if drain_on.is_set():
                    pipe.drain()
                    cluster.reconcile()
                await asyncio.sleep(0.003)

        drain_task = asyncio.create_task(drainer())

        acked: dict = {}
        failures: list = []
        dedup_pool: list = []  # (command, op_id, first_result)
        sub_set = set(subscribed)
        client_cc = cluster.client_cc

        async def writer(wid, carts, leg, keep_ops=0):
            for i, cart in enumerate(carts):
                cmd = CartAdd(int(cart), 1)
                op_id = f"op-{leg}-{wid}-{i:08d}"
                t0 = time.perf_counter()
                try:
                    val = await client_cc.call(cmd, operation_id=op_id)
                except Exception as e:  # noqa: BLE001 — every failure is a gate
                    failures.append(f"{leg} w{wid} cart {cart}: {e!r}")
                    continue
                acked[int(cart)] = acked.get(int(cart), 0) + 1
                # val is None when an ambiguous retry (timeout + owner
                # change) was absorbed by the new owner's journal — the
                # write APPLIED (the oracle below counts it) but its
                # post-write count is gone, so it can't fence visibility
                if val is not None and int(cart) in sub_set and i % 4 == 0:
                    visible.setdefault(int(cart), []).append((val, t0))
                if i < keep_ops:
                    dedup_pool.append((cmd, op_id, val))
                if i % 64 == 63:
                    await asyncio.sleep(0)

        async def run_leg(leg, total, carts_for, keep_ops=0):
            per = max(1, total // n_writers)
            t0 = time.perf_counter()
            await asyncio.gather(*(
                writer(w, carts_for(w, per), leg, keep_ops=keep_ops)
                for w in range(n_writers)
            ))
            elapsed = time.perf_counter() - t0
            cluster.client_cc.reconcile()
            pipe.drain()
            cluster.reconcile()
            return per * n_writers, elapsed

        async def drain_visible(what):
            """Every sampled write must become client-visible at the edge —
            the zero-lost-delivery gate for that leg."""
            pipe.drain()
            await until(
                lambda: not any(visible.values()), timeout_s,
                f"{what}: sampled writes client-visible",
            )

        def oracle_audit():
            lost = doubles = 0
            for cart, exp in acked.items():
                got = int(svc.counts[cart])
                if got < exp:
                    lost += 1
                elif got > exp:
                    doubles += 1
            return lost, doubles

        errors_c = global_metrics().counter("fusion_cmd_errors_total")
        retries_c = global_metrics().counter("fusion_cmd_retries_total")
        dedup_c = global_metrics().counter("fusion_cmd_dedup_total")
        eager0 = pipe.stats()["eager_waves"]
        errors0 = errors_c.value

        results: dict = {"metric": "write_path", **device, "smoke": smoke,
                         "carts": n_carts, "writers": n_writers,
                         "members": n_members, "sessions": n_sessions}

        # ========================================================== S1
        # main burst: zipf writers → commands → waves → edge fences
        note(f"S1: main burst ({n_ops} zipf increments, {n_writers} writers)...")

        def zipf_carts(w, per):
            return rng.choice(n_carts, size=per, p=weights)

        sent, elapsed = await run_leg(
            "main", n_ops, zipf_carts, keep_ops=max(1, dedup_sample_n // n_writers)
        )
        await drain_visible("S1")
        writes_per_s = round(sent / elapsed, 1)
        p50 = pctile(vis_deltas, 50)
        p99 = pctile(vis_deltas, 99)
        note(f"  {writes_per_s} writes/s; cmd→visible p50 {p50} ms, p99 {p99} ms")
        require(len(vis_deltas) > 0, "no visible-latency samples matured")
        slo.check("write.cmd_visible_p99", p99, p99_ceiling)
        lost, doubles = oracle_audit()
        slo.check_eq("write.lost", lost, 0)
        slo.check_eq("write.double_applied", doubles, 0)
        results["main"] = {"ops": sent, "writes_per_s": writes_per_s,
                           "cmd_visible_p50_ms": p50,
                           "cmd_visible_p99_ms": p99,
                           "visible_samples": len(vis_deltas)}

        # ========================================================== S2
        # hot-key write storm: every writer hammers the zipf head cart
        note(f"S2: hot-key write storm ({storm_ops} ops on cart 0)...")
        vis_deltas.clear()
        sent2, elapsed2 = await run_leg(
            "storm", storm_ops, lambda w, per: np.zeros(per, dtype=np.int64)
        )
        await drain_visible("S2")
        storm_p99 = pctile(vis_deltas, 99)
        slo.check("storm.cmd_visible_p99", storm_p99, p99_ceiling)
        lost, doubles = oracle_audit()
        slo.check_eq("storm.lost", lost, 0)
        slo.check_eq("storm.double_applied", doubles, 0)
        results["storm"] = {"ops": sent2,
                            "writes_per_s": round(sent2 / elapsed2, 1),
                            "cmd_visible_p99_ms": storm_p99}

        # ========================================================== S3
        # write-during-reshard: a member JOINS mid-burst (full runs)
        if reshard_ops > 0:
            joiner = f"m{len(cluster.refs)}"
            note(f"S3: write-during-reshard ({joiner} joins mid-burst)...")
            epoch_before = max(
                cluster.members[r].shard_map.epoch for r in cluster.live()
            )
            retries_before = retries_c.value

            async def join_mid():
                await asyncio.sleep(max(0.02, 0.1))
                await cluster.join(joiner)

            join_task = asyncio.create_task(join_mid())
            sent3, _ = await run_leg("reshard", reshard_ops, zipf_carts)
            await join_task
            await until(
                lambda: all(
                    joiner in cluster.members[r].shard_map.members
                    for r in cluster.live()
                ),
                timeout_s, "join epoch propagation",
            )
            pipe.drain()
            lost, doubles = oracle_audit()
            slo.check_eq("reshard.lost", lost, 0)
            slo.check_eq("reshard.double_applied", doubles, 0)
            epoch_after = max(
                cluster.members[r].shard_map.epoch for r in cluster.live()
            )
            require(epoch_after > epoch_before, "the join never bumped the epoch")
            results["reshard"] = {
                "ops": sent3, "joined": joiner,
                "epoch": [epoch_before, epoch_after],
                "retries": int(retries_c.value - retries_before),
            }

        # ========================================================== S4
        # write-during-host-kill: a member DIES mid-burst
        victim = next(
            r for r in cluster.live() if not cluster.members[r].is_coordinator
        )
        note(f"S4: write-during-host-kill (killing {victim} mid-burst)...")
        retries_before = retries_c.value

        async def kill_mid():
            await asyncio.sleep(0.05)
            await cluster.kill(victim)

        kill_task = asyncio.create_task(kill_mid())
        sent4, elapsed4 = await run_leg("kill", kill_ops, zipf_carts)
        await kill_task
        pipe.drain()
        lost, doubles = oracle_audit()
        slo.check_eq("kill.lost", lost, 0)
        slo.check_eq("kill.double_applied", doubles, 0)
        kill_retries = int(retries_c.value - retries_before)
        note(f"  {sent4} writes rode the kill with {kill_retries} counted retries")
        results["kill"] = {"ops": sent4, "victim": victim,
                           "retries": kill_retries,
                           "writes_per_s": round(sent4 / elapsed4, 1)}

        # ========================================================== S5
        # dedup replay: re-issue acked operation ids VERBATIM
        sample = dedup_pool[:dedup_sample_n]
        note(f"S5: dedup replay ({len(sample)} duplicate operation ids)...")
        require(len(sample) > 0, "no dedup sample collected")
        dedup_before = dedup_c.value
        counts_before = svc.counts.copy()
        for cmd, op_id, first in sample:
            replay = await client_cc.call(cmd, operation_id=op_id)
            # the shard may have MOVED since the first application (the
            # kill/join legs above): the new owner dedups via the shared
            # journal, where the original result is gone — None is the
            # honest "applied by a previous incarnation" answer. What is
            # NEVER acceptable is a second application (counts audited
            # below).
            require(
                replay == first or replay is None,
                f"dedup replay of {op_id} returned {replay} != first {first}",
            )
        absorbed = int(dedup_c.value - dedup_before)
        slo.check_eq("dedup.absorbed", absorbed, len(sample))
        require(
            bool(np.array_equal(svc.counts, counts_before)),
            "a dedup replay mutated the ledger",
        )
        results["dedup"] = {"replayed": len(sample), "absorbed": absorbed}

        # ==================================================== fusion probe
        # pause the drainer, queue a burst of commands, ONE drain: they
        # fuse into chained dispatches (the zero-extra-dispatch contract)
        note("fusion probe (drainer paused, one drain)...")
        drain_on.clear()
        await settle(0.01)
        pipe.drain()  # start from an empty pipeline
        fused_before = pipe.stats()["fused_dispatches"]
        lat_before = pipe.stats()["lat_waves"] + pipe.stats()["lat_overflow_waves"]
        probe_carts = subscribed[: min(6, len(subscribed))] or [0, 1]
        for j, c in enumerate(probe_carts):
            val = await client_cc.call(CartAdd(int(c), 1), operation_id=f"op-probe-{j}")
            acked[int(c)] = acked.get(int(c), 0) + 1
        require(
            pipe.stats()["pending_waves"] >= 2,
            "probe commands did not accumulate as pending waves",
        )
        pipe.drain()
        cluster.reconcile()
        fused_delta = pipe.stats()["fused_dispatches"] - fused_before
        lat_delta = (
            pipe.stats()["lat_waves"] + pipe.stats()["lat_overflow_waves"] - lat_before
        )
        # small command waves ride the lat kernel, the rest one fused chain
        require(
            fused_delta > 0 or lat_delta == len(probe_carts),
            "probe waves neither fused into a chain nor rode the lat mirror",
        )
        drain_on.set()
        results["fusion"] = {"probe_waves": len(probe_carts),
                             "fused_dispatches": int(fused_delta),
                             "lat_waves": int(lat_delta)}

        # ================================================== final audits
        note("final oracle + exposition audit...")
        stop_drainer = True
        await drain_task
        pipe.drain()
        cluster.reconcile()
        await settle(0.1)
        slo.check_eq("write.failed_ops", len(failures), 0)
        if failures:
            note("failures: " + "; ".join(failures[:5]))
        lost, doubles = oracle_audit()
        slo.check_eq("final.lost", lost, 0)
        slo.check_eq("final.double_applied", doubles, 0)
        # zero eager-fallback rounds attributable to the whole run
        slo.check_eq(
            "write.eager_waves", int(pipe.stats()["eager_waves"] - eager0), 0
        )
        slo.check_eq(
            "write.cmd_errors", int(errors_c.value - errors0), 0
        )
        # edge convergence: every subscribed cart's last fence serves the
        # exact final count
        stale = 0
        for ks, sub in edge._subs.items():
            cart = cart_of_key.get(ks)
            if cart is None or sub.last_frame is None or cart not in acked:
                continue
            if float(sub.last_frame[2]) != float(svc.counts[cart]):
                stale += 1
        slo.check_eq("final.stale_edge_keys", stale, 0)
        # the journal holds every acked op exactly once
        total_acked = sum(acked.values())
        require(
            log_store.last_index() >= total_acked,
            f"journal holds {log_store.last_index()} rows < {total_acked} acks",
        )
        exposition = global_metrics().render_prometheus()
        for metric in ("fusion_cmd_local_total", "fusion_cmd_forwarded_total",
                       "fusion_cmd_dedup_total", "fusion_cmd_visible_ms"):
            require(metric in exposition, f"{metric} missing from the exposition")

        stats = pipe.stats()
        results["pipeline"] = {
            "waves_submitted": stats["waves_submitted"],
            "fused_dispatches": stats["fused_dispatches"],
            "eager_waves": stats["eager_waves"],
        }
        results["total_writes"] = total_acked
        results["journal_rows"] = log_store.last_index()
        slo.enforce()
        results["slo"] = slo.checks
        results["ok"] = True
        print(json.dumps(results))
        note("done")
        await edge.close()
        await edge_rpc.stop()
        await s0.stop()
        await reader.stop()
        await cluster.stop()
        pipe.dispose()
    finally:
        set_default_hub(old)


if __name__ == "__main__":
    asyncio.run(main())
