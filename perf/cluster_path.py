#!/usr/bin/env python
"""Cluster-path measurement + smoke (ISSUE 5): routed N-server throughput
vs single-server, and REBALANCE CONVERGENCE TIME — kill one member, clock
how long until the client's map reassigns and until every subscribed key
reads oracle-correct from a surviving owner.

Flow (in-memory multi-server transport, CPU-only, no device graph — this
measures the routing/control plane, not the wave kernels):

1. **single**: one server, one plain client; CLUSTER_READS cold reads
   (unique keys — memoization would otherwise hide the RPC path) →
   ``single_reads_per_s``.
2. **routed**: CLUSTER_SERVERS servers under heartbeat membership + the
   epoch-stamped ``ShardMapRouter``; same read count →
   ``routed_reads_per_s`` + the per-peer spread (proves real fan-out).
3. **rebalance**: subscribe CLUSTER_SUBS keys, kill one member, measure
   ``reassign_ms`` (kill → client applies the new epoch; includes the
   failure-detection timeout) and ``converged_ms`` (kill → every
   subscribed key oracle-correct on a surviving owner, i.e. fencing +
   re-route + re-read all done).
4. **rolling restart** (ISSUE 6, CLUSTER_RESTART=1 default): the victim
   comes back WARM — ``warm_rejoin`` restores the durable snapshot taken
   before the kill, replays exactly the oplog tail above its watermark
   (CLUSTER_RESTART_WRITES journaled writes landed while it was down),
   re-announces, and serves; measures ``restore_to_serving_s`` and runs
   one ConsistencyAuditor sweep (zero violations required).
5. **scrape**: GET /metrics through the HTTP gateway and ASSERT the
   Prometheus exposition parses, ``fusion_shard_map_epoch`` shows the
   bumped epoch, ``fusion_resharded_keys_total`` is non-zero, and (with
   the restart phase) ``fusion_restore_replayed_entries`` > 0 — this
   doubles as the tier1 CI cluster smoke step.

Prints ONE JSON line; exits non-zero on any failed check.

Env: CLUSTER_SERVERS (3), CLUSTER_READS (600), CLUSTER_SUBS (24),
CLUSTER_SHARDS (64), CLUSTER_HEARTBEAT_S (0.05), CLUSTER_TIMEOUT_S (0.4),
CLUSTER_RESTART (1), CLUSTER_RESTART_WRITES (8).
"""
import asyncio
import dataclasses
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from stl_fusion_tpu.checkpoint import CheckpointManager  # noqa: E402
from stl_fusion_tpu.client import (  # noqa: E402
    RpcServiceMode,
    add_fusion_service,
    compute_client,
    install_compute_call_type,
)
from stl_fusion_tpu.cluster import (  # noqa: E402
    ClusterMember,
    ClusterRebalancer,
    ShardMapRouter,
    install_cluster_client,
    install_cluster_guard,
    verify_restore,
    warm_rejoin,
)
from stl_fusion_tpu.commands import command_handler  # noqa: E402
from stl_fusion_tpu.core import (  # noqa: E402
    ComputeService,
    FusionHub,
    capture,
    compute_method,
    is_invalidating,
)
from stl_fusion_tpu.oplog import (  # noqa: E402
    InMemoryOperationLog,
    LocalChangeNotifier,
    attach_operation_log,
)
from stl_fusion_tpu.rpc import (  # noqa: E402
    RpcHub,
    RpcMultiServerTestTransport,
    RpcTestTransport,
)
from stl_fusion_tpu.rpc.http_gateway import FusionHttpServer  # noqa: E402
from stl_fusion_tpu.utils.serialization import wire_type  # noqa: E402


def note(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


@wire_type("ClusterPathSet")
@dataclasses.dataclass(frozen=True)
class KvSet:
    key: str
    value: int


class Kv(ComputeService):
    def __init__(self, hub, name, store):
        super().__init__(hub)
        self.name = name
        self.store = store
        self.calls = 0

    @compute_method
    async def get(self, key: str):
        self.calls += 1
        return [self.name, self.store.get(key, 0)]

    @command_handler
    async def set_value(self, command: KvSet):
        if is_invalidating():
            await self.get(command.key)
            return
        self.store[command.key] = command.value


def build_server(ref, store, log_store=None, notifier=None, attach_reader=True):
    fusion = FusionHub()
    rpc = RpcHub(ref)
    install_compute_call_type(rpc)
    svc = Kv(fusion, ref, store)
    rpc.add_service("kv", svc)
    reader = None
    if log_store is not None:
        fusion.add_service(svc, "kv")  # named for checkpoint restore
        fusion.commander.add_service(svc)
        if attach_reader:
            reader = attach_operation_log(fusion.commander, log_store, notifier)
    return rpc, svc, fusion, reader


async def run_single(n_reads, store):
    rpc, svc, _fusion, _reader = build_server("solo", store)
    client_rpc = RpcHub("client-solo")
    install_compute_call_type(client_rpc)
    RpcTestTransport(client_rpc, rpc, wire_codec=True)
    client = compute_client("kv", client_rpc, FusionHub())
    await client.get("warm")  # dial + first-call costs out of the timing
    t0 = time.perf_counter()
    for i in range(n_reads):
        await client.get(f"s{i}")
    elapsed = time.perf_counter() - t0
    await client_rpc.stop()
    await rpc.stop()
    return n_reads / elapsed, elapsed


async def main() -> int:
    n_servers = int(os.environ.get("CLUSTER_SERVERS", 3))
    n_reads = int(os.environ.get("CLUSTER_READS", 600))
    n_subs = int(os.environ.get("CLUSTER_SUBS", 24))
    n_shards = int(os.environ.get("CLUSTER_SHARDS", 64))
    heartbeat = float(os.environ.get("CLUSTER_HEARTBEAT_S", 0.05))
    timeout = float(os.environ.get("CLUSTER_TIMEOUT_S", 0.4))
    do_restart = os.environ.get("CLUSTER_RESTART", "1") != "0"
    n_restart_writes = int(os.environ.get("CLUSTER_RESTART_WRITES", 8))
    store = {f"k{i}": i for i in range(n_subs)}

    single_rps, single_s = await run_single(n_reads, store)
    note(f"single-server: {single_rps:.0f} cold reads/s")

    # ---- routed cluster (on the shared-oplog substrate: journaled writes
    # are what the rolling-restart phase replays)
    log_store = InMemoryOperationLog()
    notifier = LocalChangeNotifier()
    refs = [f"m{i}" for i in range(n_servers)]
    hubs, services, fusions, readers, members, mesh = {}, {}, {}, {}, {}, {}
    for ref in refs:
        hubs[ref], services[ref], fusions[ref], readers[ref] = build_server(
            ref, store, log_store, notifier
        )
    for ref in refs:
        others = {r: h for r, h in hubs.items() if r != ref}
        mesh[ref] = RpcMultiServerTestTransport(hubs[ref], others, client_name=ref)
        member = ClusterMember(
            hubs[ref], ref, seeds=refs, n_shards=n_shards,
            heartbeat_interval=heartbeat, failure_timeout=timeout,
        ).install()
        install_cluster_guard(hubs[ref], member)
        members[ref] = member

    client_rpc = RpcHub("client")
    install_compute_call_type(client_rpc)
    transport = RpcMultiServerTestTransport(
        client_rpc, dict(hubs), client_name="c0", wire_codec=True
    )
    router = ShardMapRouter(client_rpc, members=refs, n_shards=n_shards)
    client_rpc.call_router = router
    install_cluster_client(client_rpc, router)
    client_fusion = FusionHub()
    rebalancer = ClusterRebalancer(client_rpc, router)
    proxy = add_fusion_service(RpcServiceMode.ROUTER, "kv", client_rpc, client_fusion)
    rebalancer.attach_proxy(proxy)

    deadline = time.monotonic() + 10
    while any(m.shard_map.epoch < 1 for m in members.values()):
        assert time.monotonic() < deadline, "bootstrap epoch never minted"
        await asyncio.sleep(0.02)
    await proxy.get("warm")  # dial + epoch sync outside the timing

    t0 = time.perf_counter()
    for i in range(n_reads):
        await proxy.get(f"r{i}")
    routed_s = time.perf_counter() - t0
    routed_rps = n_reads / routed_s
    spread = dict(router.routed_calls)
    note(f"routed x{n_servers}: {routed_rps:.0f} cold reads/s, spread {spread}")
    assert len([r for r in refs if spread.get(r)]) == n_servers, spread

    # ---- rebalance convergence
    nodes = {}
    for k in store:
        await proxy.get(k)
        nodes[k] = await capture(lambda k=k: proxy.get(k))
    victim = next(r for r in refs if not members[r].is_coordinator)

    # durable snapshot BEFORE the kill (ISSUE 6): what the rolling-restart
    # phase restores — the victim's warm computeds keyed to its current
    # (shard-map epoch, oplog watermark)
    snap_dir = tempfile.mkdtemp(prefix="fusion-cluster-restart-")
    manager = CheckpointManager(snap_dir)
    snap_watermark = readers[victim].watermark
    if do_restart:
        manager.save_durable(
            fusions[victim],
            reader=readers[victim],
            member=members[victim],
            rpc_hub=hubs[victim],
        )
        note(f"durable snapshot of {victim} at watermark {snap_watermark}")

    note(f"killing {victim}...")
    epoch_before = router.shard_map.epoch
    kill_at = time.perf_counter()
    for t in list(mesh.values()) + [transport]:
        t.servers.pop(victim, None)
    if readers[victim] is not None:
        await readers[victim].stop()
    await members[victim].dispose()
    await hubs[victim].stop()

    deadline = time.monotonic() + 30
    while victim in router.shard_map.members:
        assert time.monotonic() < deadline, router.snapshot()
        await asyncio.sleep(0.005)
    reassign_ms = (time.perf_counter() - kill_at) * 1e3

    for k in store:  # every key correct on a surviving owner
        while True:
            v = await asyncio.wait_for(proxy.get(k), 10)
            if v[0] != victim and v[1] == store[k]:
                break
            assert time.monotonic() < deadline, (k, v)
            await asyncio.sleep(0.005)
    converged_ms = (time.perf_counter() - kill_at) * 1e3
    note(
        f"rebalance: epoch {epoch_before}->{router.shard_map.epoch} in "
        f"{reassign_ms:.0f} ms, all {len(store)} keys converged in {converged_ms:.0f} ms "
        f"({rebalancer.resharded_keys} fenced)"
    )
    assert router.shard_map.epoch > epoch_before
    assert rebalancer.resharded_keys > 0
    assert victim not in proxy._clients

    # ---- rolling restart: the victim comes back WARM (ISSUE 6)
    restart = None
    if do_restart:
        # journaled writes land while the victim is down — the oplog tail
        # its warm rejoin must replay (some on keys it served warm)
        writer = min(r for r in refs if r != victim)
        warm_keys = list(store)[: max(n_restart_writes // 2, 1)]
        for n in range(n_restart_writes):
            k = warm_keys[n % len(warm_keys)] if n % 2 == 0 else f"down-{n}"
            await fusions[writer].commander.call(KvSet(k, 10_000 + n))
        expected_tail = log_store.last_index() - snap_watermark
        assert expected_tail >= n_restart_writes, (expected_tail, n_restart_writes)

        # fresh hubs (the old process is gone), transports rewired
        hubs[victim], services[victim], fusions[victim], readers[victim] = (
            build_server(victim, store, log_store, notifier, attach_reader=False)
        )
        live = [r for r in refs if r != victim]
        for r in live:
            mesh[r].servers[victim] = hubs[victim]
        transport.servers[victim] = hubs[victim]
        mesh[victim] = RpcMultiServerTestTransport(
            hubs[victim], {r: hubs[r] for r in live}, client_name=victim
        )

        note(f"warm-rejoining {victim} from snapshot...")
        t0 = time.perf_counter()
        member, reader, report = await warm_rejoin(
            fusions[victim],
            hubs[victim],
            manager,
            log_store,
            member_id=victim,
            seeds=[victim] + live,
            notifier=notifier,
            n_shards=n_shards,
            heartbeat_interval=heartbeat,
            failure_timeout=timeout,
        )
        install_cluster_guard(hubs[victim], member)
        members[victim] = member
        readers[victim] = reader
        assert report.warm, "victim came back cold (no restorable snapshot)"
        # THE acceptance arithmetic: exactly the tail above the watermark
        assert report.replayed_entries == expected_tail, report.snapshot()
        assert report.restored_nodes > 0

        deadline = time.monotonic() + 30
        while victim not in router.shard_map.members:
            assert time.monotonic() < deadline, router.snapshot()
            await asyncio.sleep(0.005)
        for k in list(store) + [f"down-{n}" for n in range(1, n_restart_writes, 2)]:
            want = store.get(k, 0)
            while True:
                v = await asyncio.wait_for(proxy.get(k), 10)
                if v[1] == want:
                    break
                assert time.monotonic() < deadline, (k, v, want)
                await asyncio.sleep(0.005)
        restore_to_serving_s = time.perf_counter() - t0
        assert restore_to_serving_s < 10.0, restore_to_serving_s

        audit = await verify_restore(fusions[victim])
        assert audit["violations"] == [], audit
        restart = {
            "restore_to_serving_s": restore_to_serving_s,
            "restore_replayed": report.replayed_entries,
            "restore_fenced": report.fenced_keys,
            "restore_violations": len(audit["violations"]),
            "restore_s": report.restore_s,
        }
        note(
            f"{victim} back warm: {report.restored_nodes} nodes restored, "
            f"{report.replayed_entries} oplog entries replayed, serving in "
            f"{restore_to_serving_s:.3f}s"
        )

    # ---- /metrics scrape through the gateway (the CI smoke assertion)
    coordinator = min(r for r in refs if r != victim)
    gateway = FusionHttpServer(hubs[coordinator])
    gateway.cluster = (members[coordinator],)
    await gateway.start()
    reader, writer = await asyncio.open_connection(gateway.host, gateway.port)
    writer.write(b"GET /metrics HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n")
    await writer.drain()
    raw = await reader.read()
    writer.close()
    body = raw.partition(b"\r\n\r\n")[2].decode()
    samples = {}
    for line in body.strip().splitlines():
        if line and not line.startswith("#"):
            name, value = line.rsplit(" ", 1)
            samples[name] = float(value)  # raises -> exposition broken
    assert samples.get("fusion_shard_map_epoch", 0) >= router.shard_map.epoch, (
        "epoch gauge not bumped in /metrics"
    )
    assert samples.get("fusion_resharded_keys_total", 0) > 0
    assert samples.get("fusion_routed_calls_total", 0) >= n_reads
    if do_restart:  # the rolling-restart CI assertion (ISSUE 6)
        assert samples.get("fusion_restore_replayed_entries", 0) > 0, (
            "fusion_restore_replayed_entries missing/zero in /metrics"
        )
        assert samples.get("fusion_restores_total", 0) >= 1
    # /shards serves the topology behind the same trust gate
    reader, writer = await asyncio.open_connection(gateway.host, gateway.port)
    writer.write(b"GET /shards HTTP/1.1\r\nHost: x\r\nConnection: close\r\n\r\n")
    await writer.drain()
    raw = await reader.read()
    writer.close()
    shards = json.loads(raw.partition(b"\r\n\r\n")[2])
    assert shards["epoch"] >= 2, shards
    if do_restart:  # the victim warm-rejoined: back in the served topology
        assert victim in shards["members"], shards
    else:
        assert victim not in shards["members"], shards
    await gateway.stop()
    note("metrics + /shards scrape ok")

    from stl_fusion_tpu.graph import require_accelerator

    out = {
        "metric": "cluster_path",
        **require_accelerator("perf/cluster_path.py"),
        "ok": True,
        "servers": n_servers,
        "n_shards": n_shards,
        "reads": n_reads,
        "single_reads_per_s": round(single_rps, 1),
        "routed_reads_per_s": round(routed_rps, 1),
        "routed_vs_single": round(routed_rps / single_rps, 3),
        "routed_spread": spread,
        "subs": len(store),
        "reassign_ms": round(reassign_ms, 1),
        "converged_ms": round(converged_ms, 1),
        "resharded_keys": rebalancer.resharded_keys,
        "failure_timeout_s": timeout,
        "epoch_final": router.shard_map.epoch,
    }
    if restart is not None:
        out["restore_to_serving_s"] = round(restart["restore_to_serving_s"], 3)
        out["restore_s"] = round(restart["restore_s"], 3)
        out["restore_replayed"] = restart["restore_replayed"]
        out["restore_fenced"] = restart["restore_fenced"]
        out["restore_violations"] = restart["restore_violations"]
    print(json.dumps(out))

    dead = set() if do_restart else {victim}
    for r, m in members.items():
        if r not in dead:
            await m.dispose()
    for r, reader in readers.items():
        if reader is not None and r not in dead:
            await reader.stop()
    await client_rpc.stop()
    for r, h in hubs.items():
        if r not in dead:
            await h.stop()
    import shutil

    shutil.rmtree(snap_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
