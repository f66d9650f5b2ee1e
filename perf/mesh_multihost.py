#!/usr/bin/env python
"""True multi-host mesh legs (ISSUE 15): real OS-process boundaries.

Orchestrates 2+ emulated HOST processes (cluster/multihost.py:
``jax.distributed`` + gloo CPU collectives, one XLA CPU device pool per
process) running the routed graph with the hierarchical exchange, and
gates the claims PR 9 could only count:

1. **Scale leg** — a power-law graph of ``MESH_MH_NODES`` split across
   the hosts, ``exchange="hier"`` (intra-host subgroup a2a + inter-host
   host-bucket ppermute tree): wave 0 is oracle-checked against the
   vectorized host BFS IN the workers, and its packed mask is exported so
   the parent cross-checks it against the SINGLE-PROCESS routed oracle —
   two processes and one process must produce the bit-identical frontier.
   Then fused chain rounds measure throughput, a patch burst FORCES a
   bucket/edge-slack overflow that must resolve by counted in-place
   resize (zero rebuilds in steady state), and a DCN leg posts a fence to
   an off-mesh member over a real TCP socket between the two host
   processes (``fusion_mesh_dcn_fallback_total`` EXERCISED, not merely
   counted).

2. **Elastic chaos ladder (ISSUE 16)** — the survivor NEVER restarts.
   Each ``elastic`` host forms the world, runs round 0 attached (warming
   the gloo communicators), then DETACHES the coordination agent
   (``detach_world`` — a peer death no longer aborts survivors) and hands
   membership to :class:`~stl_fusion_tpu.cluster.mesh_controller.
   MeshController`. The parent SIGKILLs host 1 mid-burst (timing from the
   ``host_kill_reform`` ChaosPolicy): the survivor's evidence converges
   (round-deadline overrun on the wedged dispatch thread + heartbeat
   lapse + the orchestrator's dead flag), it DEGRADES in-process (counted
   ``mesh_degraded``, local serving continues), re-forms over the
   survivors via the rendezvous board's counted election ladder, rebuilds
   graph+placement for the new member set, restores every host's last
   committed snapshot, and REPLAYS from the minimum committed round — the
   first oracle-exact wave stamps ``host_kill_recovery_s`` (gate: under
   ``MESH_MH_RECOVERY_BUDGET_S``). The FLAP rung relaunches host 1 as a
   live JOINER moments later: members absorb it at an agreed round
   boundary (re-form to N+1, boundary snapshots rebalance the shards) and
   the schedule finishes on both hosts with zero divergent waves. A
   separate JOIN leg grows 2 → 3 hosts live (non-power-of-2: the hier
   exchange resolves via the counted gather fallback), and a PARTITION
   leg (``mesh_partition`` policy) proves a lone heartbeat lapse rides
   through without a degrade.

3. **Geometry certify legs** — ``MESH_MH_GEOMETRIES`` (default "4,3")
   re-runs the scale oracle at each emulated host count: 4 (and 8 in the
   record protocol) certify the hierarchical exchange past 2 hosts;
   3 certifies the non-power-of-2 gather fallback, counted and exact.

Run as orchestrator: ``python perf/mesh_multihost.py`` (or via
perf/mesh_path.py with ``MESH_MULTIHOST=2``). The worker entry is this
same file with ``--worker`` (the launcher env carries the rest).

Env: MESH_MULTIHOST (2), MESH_MH_DPH (2), MESH_MH_NODES (40_000),
MESH_MH_SHARDS (64), MESH_MH_ROUNDS (4), MESH_MH_SEEDS_PER_ROUND (4),
MESH_MH_EXCHANGE (hier), MESH_MH_SCALE (1), MESH_MH_ELASTIC (1),
MESH_MH_JOIN3 (1), MESH_MH_PARTITION (1), MESH_MH_GEOMETRIES (4,3),
MESH_MH_RECOVERY_BUDGET_S (15), MESH_MH_JOIN_BUDGET_S (30),
MESH_MH_EXPECT_JOINS (0: members hold the last MESH_MH_JOIN_RESERVE (2)
rounds until that many scripted joiners are absorbed — smoke schedules
otherwise finish before a joiner's interpreter is up; violation after
MESH_MH_JOIN_HOLD_S (180)), MESH_MH_GEOM_NODES (12000),
MESH_MH_XCHECK (1: parent single-process oracle cross-check),
MESH_MH_TIMEOUT (600s per phase).
"""
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def _env_int(name: str, default: int) -> int:
    return int(os.environ.get(name, str(default)))


# the ONE oracle BFS both perf gates share (mesh_path is importable in
# both entry modes: worker runs from perf/, orchestrator imports us lazily)
from mesh_path import compact_trace, numpy_bfs_mask  # noqa: E402


def _put_file(path: str, content: str) -> None:
    """Atomic rendezvous-file write: the peer polls on existence and then
    parses ONCE — a plain open/write exposes a zero-byte window between
    create and flush that crashes the reader (int('') / json.loads(''))."""
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(content)
    os.replace(tmp, path)


def round_seeds(rng_seed: int, n: int, rounds: int, per_round: int, stages: int):
    """The deterministic burst schedule every phase re-derives: round r =
    ``stages`` chain stages of ``per_round`` seeds each."""
    rng = np.random.default_rng(rng_seed)
    return [
        [rng.choice(n, size=per_round, replace=False).tolist() for _ in range(stages)]
        for _ in range(rounds)
    ]


# ===================================================================== worker
def _watchdog(mh_dir: str, deadline_holder: list) -> None:
    """Daemon thread: a parent 'peer-dead' flag or a wedged collective
    (round overrunning its deadline) hard-exits the process — a killed
    peer leaves gloo collectives stuck in C++ where no Python exception
    can reach. Exit code 3 = 'peer lost, state on disk'."""
    flag = os.path.join(mh_dir, "peer-dead")
    while True:
        time.sleep(0.2)
        if os.path.exists(flag):
            os._exit(3)
        dl = deadline_holder[0]
        if dl is not None and time.time() > dl:
            os._exit(3)


async def _dcn_leg(ctx, mh_dir: str, result: dict) -> None:
    """The real-DCN marker (ISSUE 15 satellite): host 0 serves a live
    mini-hub whose fan-out scope marks host 1's member OFF-mesh; host 1
    subscribes over a real TCP socket and must observe the fence. The
    DCN relay therefore crosses an actual process boundary and
    ``fusion_mesh_dcn_fallback_total`` is exercised, not merely counted."""
    import asyncio

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
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.rpc import RpcHub
    from stl_fusion_tpu.rpc.fanout import install_compute_fanout
    from stl_fusion_tpu.rpc.tcp import RpcTcpServer, tcp_client_connector

    members = ctx.member_names()
    port_file = os.path.join(mh_dir, "dcn-port")
    sub_file = os.path.join(mh_dir, "dcn-subscribed")
    ack_file = os.path.join(mh_dir, "dcn-ack")

    async def _wait_for(path: str, timeout: float = 60.0) -> str:
        # MUST yield to the loop: the server host sits in this wait while
        # its RpcTcpServer serves the peer's subscribe — a blocking sleep
        # here deadlocks both hosts (the FL004 frozen-pump class)
        t0 = time.time()
        while not os.path.exists(path):
            if time.time() - t0 > timeout:
                raise TimeoutError(f"rendezvous file {path} never appeared")
            await asyncio.sleep(0.05)
        with open(path) as f:
            return f.read()

    if ctx.process_id == 0:
        ns = 256
        hub = FusionHub()
        old = set_default_hub(hub)
        try:
            backend = TpuGraphBackend(hub, node_capacity=ns + 16, edge_capacity=256)

            class RowSvc(ComputeService):
                def load(self, ids):
                    return np.asarray(ids, dtype=np.float32)

                @compute_method(table=TableBacking(rows=ns, batch="load"))
                async def row(self, i: int) -> float:
                    return float(i)

            svc = RowSvc(hub)
            hub.add_service(svc)
            table = memo_table_of(svc.row)
            blk = backend.bind_table_rows(table)
            table.read_batch(np.arange(ns))
            backend.flush()
            server_rpc = RpcHub("server")
            install_compute_call_type(server_rpc)
            server_rpc.add_service("rows", svc)
            fanout = install_compute_fanout(server_rpc, backend)
            # host 0's member is ON this host's mesh scope; host 1's is a
            # cluster member on ANOTHER host — the legitimate DCN path
            fanout.set_mesh_scope([members[0]], cluster_members=members)
            server = await RpcTcpServer(server_rpc, ref_prefix="").start()
            _put_file(port_file, str(server.port))
            await _wait_for(sub_file)
            backend.cascade_rows_batch(blk, [5])
            await asyncio.sleep(0)  # let the outbox drain post
            ack = json.loads(await _wait_for(ack_file, timeout=60.0))
            result["dcn"] = {
                "dcn_fallback_relays": fanout.dcn_fallback_relays,
                "mesh_member_relays": fanout.mesh_member_relays,
                "client_observed_fence": bool(ack.get("invalidated")),
            }
            fanout.dispose()
            await server_rpc.stop()
            await server.stop()
        finally:
            set_default_hub(old)
    elif ctx.process_id == 1:
        port = int(await _wait_for(port_file))
        client_rpc = RpcHub(f"{members[1]}-rpc")
        install_compute_call_type(client_rpc)
        client_rpc.client_connector = tcp_client_connector(
            "127.0.0.1", port, client_id=members[1]
        )
        client = compute_client("rows", client_rpc, FusionHub())
        got = await client.row(5)
        node = await capture(lambda: client.row(5))
        _put_file(sub_file, "1")
        invalidated = True
        try:
            await asyncio.wait_for(node.when_invalidated(), 30.0)
        except (asyncio.TimeoutError, TimeoutError):
            # asyncio.TimeoutError is not the builtin before 3.11
            invalidated = False
        _put_file(ack_file, json.dumps({"invalidated": invalidated, "value": got}))
        result["dcn"] = {"client_observed_fence": invalidated}
        await client_rpc.stop()


def run_worker() -> int:
    import threading

    from stl_fusion_tpu.checkpoint import restore_mesh_shards, save_mesh_shards
    from stl_fusion_tpu.cluster import DevicePlacement, ShardMap
    from stl_fusion_tpu.cluster.multihost import async_depth_env, init_multihost
    from stl_fusion_tpu.graph.synthetic import power_law_dag

    phase = os.environ.get("MESH_MH_PHASE", "scale")
    mh_dir = os.environ["MESH_MH_DIR"]
    n = _env_int("MESH_MH_NODES", 40_000)
    n_shards = _env_int("MESH_MH_SHARDS", 64)
    exchange = os.environ.get("MESH_MH_EXCHANGE", "hier")
    async_depth = async_depth_env()
    rounds_total = _env_int("MESH_MH_ROUNDS", 4)
    per_round = _env_int("MESH_MH_SEEDS_PER_ROUND", 4)
    stages = _env_int("MESH_MH_STAGES", 2)
    start_round = _env_int("MESH_MH_START_ROUND", 0)
    end_round = _env_int("MESH_MH_END_ROUND", rounds_total)
    restore_from = os.environ.get("MESH_MH_RESTORE", "")
    all_members = os.environ["MESH_MH_MEMBERS"].split(",")
    round_deadline_s = float(os.environ.get("MESH_MH_ROUND_DEADLINE", "120"))

    ctx = init_multihost()
    from stl_fusion_tpu.parallel import RoutedShardedGraph

    result: dict = {
        "phase": phase,
        "host": ctx.process_id,
        "n_hosts": ctx.n_hosts,
        "devices_per_host": ctx.devices_per_host,
        "violations": [],
    }
    deadline_holder = [None]
    threading.Thread(
        target=_watchdog, args=(mh_dir, deadline_holder), daemon=True
    ).start()

    t0 = time.time()
    src, dst = power_law_dag(n, avg_degree=3.0, seed=7)
    gen_s = time.time() - t0
    # the phase's member view: survivors only in the survivor phase; the
    # shard map DIFF from the full membership is what reassigns the dead
    # host's shards (PR 5 machinery, real this time)
    live_members = all_members[: ctx.n_hosts]
    smap = ShardMap.initial(all_members, n_shards=n_shards)
    if live_members != all_members:
        smap = smap.with_members(live_members)
    t0 = time.time()
    placement = DevicePlacement.build(
        smap, ctx.n_dev, n, mesh_members=live_members,
        devices_per_host=ctx.devices_per_host,
    )
    graph = RoutedShardedGraph(
        src, dst, n, placement, mesh=ctx.mesh(), exchange=exchange,
        exchange_async=async_depth > 0, async_depth=async_depth,
    )
    build_s = time.time() - t0
    log(
        f"[h{ctx.process_id}/{phase}] {n} nodes, {len(src)} edges over "
        f"{ctx.n_hosts} host(s) x {ctx.devices_per_host} dev; build {build_s:.1f}s "
        f"(e_cap {graph.e_cap}, bucket {graph.bucket_cap}, hbucket {graph.hbucket_cap})"
    )
    result.update(
        nodes=n, edges=int(len(src)), exchange=graph.exchange,
        gen_s=round(gen_s, 1), build_s=round(build_s, 1),
    )

    if restore_from:
        restored = 0
        for path in sorted(restore_from.split(",")):
            if os.path.exists(path):
                restored += restore_mesh_shards(graph, path)["restored"]
        result["restored_shards"] = restored
        if restored == 0:
            result["violations"].append("warm-rejoin restored zero shards")

    schedule = round_seeds(123, n, rounds_total, per_round, stages)
    # per-stage count oracles re-BFS per stage — exact but O(rounds·BFS);
    # phases that warm-start from snapshots (whose restored state may run
    # AHEAD of the replay start: monotone, still ⊆ the final closure) and
    # the 100M record leg gate on the phase-end FULL-MASK equality instead
    check_stages = os.environ.get("MESH_MH_STAGE_ORACLE", "1") == "1"
    # the oracle's memory: every seed of every round ALREADY run (prior
    # phases included — the restored snapshot carries their cascades)
    flat = [s for r in schedule[:start_round] for st in r for s in st]
    mask_know = numpy_bfs_mask(src, dst, n, flat) if check_stages else None
    divergence = 0
    chain_dispatches = 0
    t_run = time.time()
    for r in range(start_round, end_round):
        deadline_holder[0] = time.time() + round_deadline_s
        # every host pins the SAME deterministic cause for round r, so the
        # per-host trace segments stitch into one cross-host wave timeline
        graph.trace_cause = f"mesh-wave/{phase}#r{r}"
        pending = graph.dispatch_union_chain(schedule[r])
        counts, stage_ids, info = graph.harvest_union_chain(pending)
        chain_dispatches += 1
        if check_stages:
            seen = set(np.nonzero(mask_know)[0].tolist())
            for st, c in zip(schedule[r], counts):
                want = {
                    x
                    for x in np.nonzero(numpy_bfs_mask(src, dst, n, st))[0].tolist()
                    if x not in seen
                }
                seen |= want
                if int(c) != len(want):
                    divergence += 1
            mask_know = np.zeros(n, dtype=bool)
            mask_know[np.fromiter(seen, dtype=np.int64, count=len(seen))] = True
        deadline_holder[0] = None
        if os.environ.get("MESH_MH_SNAPSHOT", "0") == "1":
            snap = os.path.join(mh_dir, f"snap_h{ctx.process_id}.npz")
            save_mesh_shards_local(graph, snap, save_mesh_shards)
            _put_file(
                os.path.join(mh_dir, f"progress_h{ctx.process_id}"), str(r + 1)
            )
    burst_s = time.time() - t_run
    graph.trace_cause = None  # later legs mint their own wave causes
    rounds_run = end_round - start_round
    if mask_know is None:
        flat_all = [s for r_ in schedule[:end_round] for st in r_ for s in st]
        mask_know = numpy_bfs_mask(src, dst, n, flat_all)

    # phase-end oracle: the resident mask must EXACTLY equal the BFS
    # closure of every seed so far — zero oracle-divergent waves
    mask = graph.invalid_mask()
    oracle_exact = bool(np.array_equal(mask, mask_know))
    if not oracle_exact:
        result["violations"].append(
            f"phase-end mask diverged at {int((mask != mask_know).sum())} node(s)"
        )
    if divergence:
        result["violations"].append(f"{divergence} chain stage(s) diverged")
    result.update(
        rounds=rounds_run,
        burst_s=round(burst_s, 2),
        oracle_exact=oracle_exact,
        chain_dispatches=chain_dispatches,
        divergence=divergence,
        serving_ts=time.time(),  # first oracle-exact service of this phase
    )

    # fleet telemetry + trace stitch (ISSUE 18): every host publishes its
    # registry snapshot + trace segments onto the board, then host 0
    # aggregates, asserts the merge semantics and stitches the last round
    ctx.sync("pre-telemetry")
    _telemetry_leg(ctx, mh_dir, phase, live_members, end_round, result)
    ctx.sync("post-telemetry")

    if phase == "scale":
        # wave-0 packed mask export: the parent cross-checks it against
        # the SINGLE-PROCESS routed oracle (acceptance: bit-identical)
        if ctx.process_id == 0:
            np.save(
                os.path.join(mh_dir, "wave_mask.npy"), np.packbits(mask)
            )
        # resize leg: flood one destination's slack past e_cap — must
        # resolve by counted in-place resize, zero rebuild-grade failures.
        # MESH_MH_RESIZE=0 skips it (the flood is e_cap-sized: a python
        # slot-assignment loop that is fine at smoke scale and hours at
        # the 100M record's ~50M-entry slack — the CI smoke owns this gate)
        if os.environ.get("MESH_MH_RESIZE", "1") == "1":
            _resize_leg(graph, src, dst, n, mask_know, result)
        # DCN leg: a fence relayed to the OTHER host process over TCP
        # (geometry certify legs skip it — it is a 2-host protocol)
        if os.environ.get("MESH_MH_DCN", "1") == "1":
            ctx.sync("pre-dcn")
            import asyncio

            asyncio.run(_dcn_leg(ctx, mh_dir, result))
            ctx.sync("post-dcn")

    st = graph.stats()
    result["stats"] = {
        k: st[k]
        for k in (
            "exchange", "hosts", "waves_run", "exchange_levels_total",
            "cross_host_words", "cross_words_per_level", "bucket_resizes",
            "hier_fallbacks", "e_cap", "bucket_cap", "hbucket_cap",
            "exchange_async", "async_depth", "quiescence_checks",
            "spec_levels_total",
        )
    }
    if async_depth > 0 and graph.quiescence_checks == 0:
        result["violations"].append(
            "async requested but zero quiescence checks ran (silent sync)"
        )
    result["inv_per_s"] = round(int(mask_know.sum()) / max(burst_s, 1e-9), 1)
    if graph.cross_words_per_level == 0 and ctx.n_hosts > 1:
        result["violations"].append("zero cross-host exchange words")
    if chain_dispatches == 0:
        result["violations"].append("zero fused chain dispatches")
    with open(
        os.path.join(mh_dir, f"result_{phase}_h{ctx.process_id}.json"), "w"
    ) as f:
        json.dump(result, f)
    ctx.shutdown()
    return 0 if not result["violations"] else 1


def _resize_leg(graph, src, dst, n, mask_know, result: dict) -> None:
    """Steady-state overflow: flood one destination's slack past e_cap —
    must resolve by counted in-place resize with the grown layout still
    oracle-exact; a rebuild-grade failure is a gate violation."""
    rng = np.random.default_rng(77)
    k = graph.e_cap + 64
    u = rng.integers(0, n - 1, size=k)
    v = np.full(k, n - 1, dtype=np.int64)
    ok = graph.patch_batch(np.empty(0, np.int64), u, v, np.zeros(k, np.int32))
    if not ok:
        result["violations"].append("steady-state patch fell to the rebuild rung")
    if graph.bucket_resizes == 0:
        result["violations"].append("overflow resolved without a counted resize")
    adj_extra = numpy_bfs_mask(
        np.concatenate([src, u.astype(np.int32)]),
        np.concatenate([dst, v.astype(np.int32)]),
        n,
        [int(u[0])],
    )
    _c2, _ids2, over2 = graph.run_wave_collect([int(u[0])])
    grown_mask = graph.invalid_mask()
    want2 = mask_know | adj_extra
    if over2 or not np.array_equal(grown_mask, want2):
        result["violations"].append("post-resize wave diverged from oracle")
    result["resize"] = {
        "bucket_resizes": graph.bucket_resizes,
        "detail": graph.stats()["resize_detail"],
        "post_resize_oracle_exact": bool(np.array_equal(grown_mask, want2)),
    }


def _telemetry_leg(ctx, mh_dir: str, phase: str, live_members, end_round: int,
                   result: dict) -> None:
    """Mesh telemetry over a REAL process boundary (ISSUE 18 tentpole c):
    each host publishes its registry snapshot + trace segments onto the
    rendezvous board; host 0 aggregates, asserts the merge is honest (SUM
    of a known counter matches the per-host scrapes exactly, both host
    labels present, nobody stale), and stitches the last round's wave into
    ONE cross-host timeline with a straggler table."""
    from stl_fusion_tpu.cluster.mesh_controller import RendezvousBoard
    from stl_fusion_tpu.diagnostics.mesh_telemetry import (
        MeshTelemetryAggregator,
        MeshTelemetryPublisher,
        global_mesh_trace,
    )

    member = f"h{ctx.process_id}"
    board = RendezvousBoard(os.path.join(mh_dir, "tboard"))
    pub = MeshTelemetryPublisher(member=member, period_s=5.0)
    payload = pub.publish_board(board)
    ctx.sync("telemetry-published")
    if ctx.process_id != 0:
        return
    agg = MeshTelemetryAggregator(local_member=member, period_s=5.0)
    agg.sync_board(board)
    missing = sorted(set(live_members) - set(agg.known_hosts()))
    if missing:
        result["violations"].append(
            f"mesh telemetry: no snapshot from {missing}"
        )
    per_host, merged, stale = agg.merged_samples()
    if stale:
        result["violations"].append(
            f"mesh telemetry: live host(s) marked stale: {sorted(stale)}"
        )
    # SUM semantics, asserted against the per-host scrapes: the wave
    # counter exists on every host that ran the burst
    probe = "fusion_mesh_trace_segments_total"
    want = sum(per_host[h].get(probe, 0.0) for h in per_host if h not in stale)
    got = merged.get(probe, 0.0)
    sum_exact = got == want and want > 0
    if not sum_exact:
        result["violations"].append(
            f"mesh-telemetry-sum-mismatch: merged {probe}={got}, "
            f"per-host sum={want}"
        )
    text = agg.render_mesh_prometheus()
    labels_ok = all(f'host="{h}"' in text for h in live_members)
    if not labels_ok:
        result["violations"].append(
            "mesh telemetry: merged exposition missing a host= label"
        )
    result["mesh_telemetry"] = {
        "hosts": agg.known_hosts(),
        "stale": sorted(stale),
        "sum_exact": sum_exact,
        "merged_series": len(merged),
        "exposition_lines": text.count("\n"),
        "snapshot_series": len(payload.get("series") or ()),
    }
    # mesh-scope health verdict (ISSUE 19 CI gate): every live host's
    # shipped verdict folds worst-wins; the run fails if the fleet is
    # anything but ok or any merge leaned on a stale snapshot
    health = agg.mesh_health()
    if health["verdict"] != "ok":
        result["violations"].append(
            f"mesh health: {health['verdict']} "
            f"(triggered by {health.get('triggered_by')} "
            f"on {health.get('triggered_host')})"
        )
    if health["stale"]:
        result["violations"].append(
            f"mesh health: verdict merged over stale host(s) {health['stale']}"
        )
    result["health"] = {
        "verdict": health["verdict"],
        "hosts": {m: e["verdict"] for m, e in health["hosts"].items()},
        "stale": health["stale"],
    }
    # workload attribution digest (ISSUE 19): the mesh-merged top key per
    # domain — compact (one entry per domain), diffable release over release
    hot = agg.hotkeys_report(n=1)
    result["hotkeys"] = {
        d: {
            "total": body["total"],
            "top_key": body["top"][0]["key"] if body["top"] else None,
            "top_share": body["top"][0]["share"] if body["top"] else None,
        }
        for d, body in (hot.get("domains") or {}).items()
    }
    # stitch the LAST round's wave: both hosts pinned the same cause
    cause = f"mesh-wave/{phase}#r{end_round - 1}"
    stitched = global_mesh_trace().stitch(cause, expected_hosts=list(live_members))
    if stitched is None:
        result["violations"].append(f"mesh telemetry: no trace for {cause}")
        return
    if stitched["partial"]:
        result["violations"].append(
            f"mesh telemetry: PARTIAL stitch, missing {stitched['missing_hosts']}"
        )
    if not stitched["levels"]:
        result["violations"].append("mesh telemetry: stitched timeline has no levels")
    # the FULL stitched timeline rides the worker result file (the
    # tools/trace_dump.py input); the orchestrator compacts it for the
    # bench-record-sized mesh section
    result["trace"] = stitched


def save_mesh_shards_local(graph, path: str, save_fn) -> None:
    """Per-host snapshot: only the shards THIS host's devices own (the
    honest per-shard unit of the chaos ladder) — written atomically via
    the checkpoint helper on a local-only export."""
    snap = graph.export_shard_state(local_only=True)

    class _Shim:
        def export_shard_state(self):
            return snap

    save_fn(_Shim(), path)


# =============================================================== elastic worker
def _read_json(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError):
        return None


def _wait_json(path: str, timeout_s: float = 180.0) -> dict:
    t0 = time.time()
    while True:
        rec = _read_json(path)
        if rec is not None:
            return rec
        if time.time() - t0 > timeout_s:
            raise TimeoutError(f"rendezvous file {path} never appeared")
        time.sleep(0.05)


def run_elastic_worker() -> int:
    """One ELASTIC host process (ISSUE 16): survives peer death, flap and
    live join WITHOUT restarting.

    Round 0 runs attached (compiling the chain program and warming the
    gloo communicators), then the coordination agent is DETACHED — from
    that moment the MeshController owns membership. Every later round
    dispatches on a worker thread under a deadline: an overrun is counted
    evidence (the wedged-collective tell), and when independent signals
    converge on a peer the survivor degrades in-process (the wedged
    thread is the documented zombie), re-forms over the survivors via the
    board's counted election ladder, rebuilds graph+placement for the new
    member set, restores every host's last committed snapshot and replays
    from the minimum committed round — the first oracle-exact wave stamps
    the ``recovered-*`` file the orchestrator gates on. Pending JOINs
    absorb at a PLANNED round boundary (the lowest-ranked member writes
    the plan one boundary ahead, so collectively-synchronized members
    never split-brain on when to re-form): members snapshot, re-form to
    N+k, and everyone — joiner included — restores and continues the same
    schedule, zero divergent waves."""
    import threading

    from stl_fusion_tpu.checkpoint import restore_mesh_shards, save_mesh_shards
    from stl_fusion_tpu.cluster import DevicePlacement, ShardMap
    from stl_fusion_tpu.cluster.mesh_controller import (
        JaxWorldOps,
        MeshController,
        RendezvousBoard,
    )
    from stl_fusion_tpu.cluster.multihost import (
        ENV_DEVICES_PER_HOST,
        ENV_PROCESS_ID,
        async_depth_env,
        init_multihost,
        teardown_world,
    )
    from stl_fusion_tpu.graph.synthetic import power_law_dag
    from stl_fusion_tpu.parallel import RoutedShardedGraph, graph_mesh
    from stl_fusion_tpu.resilience.events import global_events

    mh_dir = os.environ["MESH_MH_DIR"]
    n = _env_int("MESH_MH_NODES", 40_000)
    n_shards = _env_int("MESH_MH_SHARDS", 64)
    exchange = os.environ.get("MESH_MH_EXCHANGE", "hier")
    async_depth = async_depth_env()
    rounds_total = _env_int("MESH_MH_ROUNDS", 6)
    per_round = _env_int("MESH_MH_SEEDS_PER_ROUND", 4)
    stages = _env_int("MESH_MH_STAGES", 2)
    round_deadline_s = float(os.environ.get("MESH_MH_ROUND_DEADLINE", "6"))
    hb_timeout_s = float(os.environ.get("MESH_MH_HB_TIMEOUT", "2"))
    all_members = os.environ["MESH_MH_MEMBERS"].split(",")
    is_joiner = os.environ.get("MESH_MH_JOINER", "0") == "1"
    absorb = os.environ.get("MESH_MH_ABSORB", "1") == "1"
    partition_target = os.environ.get("MESH_MH_PARTITION_TARGET", "")
    # scripted-join pacing: members expecting a live joiner RESERVE the
    # last rounds, holding that boundary until the join is absorbed — a
    # smoke-scale schedule finishes in under a second, long before the
    # joiner's interpreter is even up
    expect_joins = 0 if is_joiner else _env_int("MESH_MH_EXPECT_JOINS", 0)
    join_reserve = _env_int("MESH_MH_JOIN_RESERVE", 2)
    join_hold_s = float(os.environ.get("MESH_MH_JOIN_HOLD_S", "180"))
    dph = int(os.environ[ENV_DEVICES_PER_HOST])

    if is_joiner:
        member_id = os.environ["MESH_MH_MEMBER_ID"]
    else:
        member_id = all_members[int(os.environ.get(ENV_PROCESS_ID, "0"))]

    from stl_fusion_tpu.diagnostics.mesh_telemetry import (
        MeshTelemetryAggregator,
        MeshTelemetryPublisher,
    )

    board = RendezvousBoard(os.path.join(mh_dir, "board"))
    # fleet plane rides the SAME board that carries the election ladder:
    # the telemetry channel must survive the degrade window (ISSUE 18)
    telem_pub = MeshTelemetryPublisher(member=member_id, period_s=1.0)
    telem_agg = MeshTelemetryAggregator(local_member=member_id, period_s=1.0)
    events = global_events()
    ops = JaxWorldOps(dph)
    src, dst = power_law_dag(n, avg_degree=3.0, seed=7)
    schedule = round_seeds(123, n, rounds_total, per_round, stages)
    result: dict = {
        "phase": "elastic",
        "member": member_id,
        "joiner": is_joiner,
        "violations": [],
        "recoveries": [],
        "joins": [],
    }
    stop_beats = threading.Event()
    hold_beats = threading.Event()

    def _closure(upto: int):
        flat = [s for rr in schedule[:upto] for st in rr for s in st]
        return numpy_bfs_mask(src, dst, n, flat)

    def _progress(m: str) -> int:
        try:
            with open(os.path.join(mh_dir, f"progress_{m}")) as f:
                return int(f.read() or 0)
        except OSError:
            return 0

    g = None
    ctl = None
    divergence = 0
    r = 0
    try:
        if is_joiner:
            # form FIRST, touch jax after: a pre-existing local backend
            # would ignore the gloo collectives config form_world installs
            ctl = MeshController(
                member_id, [member_id], board, ops, events=events,
                heartbeat_timeout_s=hb_timeout_s,
            )
            world = ctl.join(
                timeout_s=float(os.environ.get("MESH_MH_JOIN_TIMEOUT", "180"))
            )
            r = int(
                _wait_json(os.path.join(mh_dir, f"resume-{ctl.epoch}.json"))["round"]
            )
        else:
            ctx = init_multihost()
            ctl = MeshController(
                member_id, all_members[: ctx.n_hosts], board, ops,
                events=events, heartbeat_timeout_s=hb_timeout_s,
            )
            world = ctl.adopt_world(ctx)
        log(f"[{member_id}/elastic] epoch {ctl.epoch} members={ctl.members}")

        def _beater():
            while not stop_beats.wait(0.3):
                if not hold_beats.is_set():
                    ctl.beat()

        threading.Thread(target=_beater, daemon=True, name="mesh-beater").start()

        def _build(live):
            t0 = time.time()
            smap = ShardMap.initial(all_members, n_shards=n_shards)
            if list(live) != list(all_members):
                smap = smap.with_members(list(live))
            placement = DevicePlacement.build(
                smap, len(live) * dph, n, mesh_members=list(live),
                devices_per_host=dph,
            )
            built = RoutedShardedGraph(
                src, dst, n, placement, mesh=graph_mesh(), exchange=exchange,
                exchange_async=async_depth > 0, async_depth=async_depth,
            )
            log(
                f"[{member_id}/elastic] graph over {list(live)} in "
                f"{time.time() - t0:.1f}s (exchange {built.exchange})"
            )
            return built

        def _restore(into, members, *, only_progress=None) -> int:
            restored = 0
            for m in members:
                if only_progress is not None and _progress(m) != only_progress:
                    continue  # a stale flap-era snapshot must not shadow fresh bits
                path = os.path.join(mh_dir, f"snap_{m}.npz")
                if os.path.exists(path):
                    restored += restore_mesh_shards(into, path)["restored"]
            return restored

        def _commit_snapshot(committed: int) -> None:
            save_mesh_shards_local(
                g, os.path.join(mh_dir, f"snap_{member_id}.npz"), save_mesh_shards
            )
            _put_file(os.path.join(mh_dir, f"progress_{member_id}"), str(committed))
            telem_pub.publish_board(board)  # fleet snapshot rides each commit

        def _full_mask_check(upto: int, what: str) -> bool:
            want = _closure(upto)
            got = g.invalid_mask()
            ok = bool(np.array_equal(got, want))
            if not ok:
                result["violations"].append(
                    f"{what}: mask diverged at {int((got != want).sum())} node(s)"
                )
            return ok

        mask_know = _closure(r)

        def _stage_check(round_idx: int, counts) -> None:
            nonlocal mask_know, divergence
            seen = set(np.nonzero(mask_know)[0].tolist())
            for st, c in zip(schedule[round_idx], counts):
                want = {
                    x
                    for x in np.nonzero(
                        numpy_bfs_mask(src, dst, n, st)
                    )[0].tolist()
                    if x not in seen
                }
                seen |= want
                if int(c) != len(want):
                    divergence += 1
            mask_know = np.zeros(n, dtype=bool)
            mask_know[np.fromiter(seen, dtype=np.int64, count=len(seen))] = True

        # detach must WAIT until one real chain round has run in a fresh
        # world: new gloo communicators rendezvous through the agent's KV
        # store, so the first round after any (re-)form runs attached
        pending_detach = False
        if is_joiner:
            g = _build(ctl.members)
            result["restored_shards"] = _restore(g, ctl.members, only_progress=r)
            world.sync("post-join")
            ok = _full_mask_check(r, "joiner warm start")
            mask_know = _closure(r)
            _put_file(
                os.path.join(mh_dir, f"rebalanced-{member_id}"),
                json.dumps({"ts": time.time(), "round": r, "oracle_exact": ok}),
            )
            pending_detach = True
        else:
            g = _build(ctl.members)
            # round 0 runs ATTACHED: it compiles the chain program and
            # warms the gloo communicators that must outlive the agent
            counts, _ids, _info = g.harvest_union_chain(
                g.dispatch_union_chain(schedule[0])
            )
            _stage_check(0, counts)
            r = 1
            _commit_snapshot(r)
            if world.is_multiprocess:
                ctl.detach()
            _put_file(os.path.join(mh_dir, f"detached-{member_id}"), "1")

        recovery_target = None  # committed-round count that completes a recovery

        def _stamp_recovery() -> None:
            nonlocal recovery_target, mask_know
            ok = _full_mask_check(r, "recovery")
            mask_know = _closure(r)
            _put_file(
                os.path.join(mh_dir, f"recovered-{member_id}"),
                json.dumps({"ts": time.time(), "round": r, "oracle_exact": ok}),
            )
            recovery_target = None

        def _dispatch_with_deadline(graph_now, round_idx):
            holder = {"done": threading.Event(), "counts": None, "err": None}

            def _run():
                try:
                    pending = graph_now.dispatch_union_chain(schedule[round_idx])
                    holder["counts"] = graph_now.harvest_union_chain(pending)[0]
                except BaseException as e:  # noqa: BLE001 — the zombie reports, never raises
                    holder["err"] = repr(e)
                finally:
                    holder["done"].set()

            threading.Thread(
                target=_run, daemon=True, name=f"dispatch-r{round_idx}"
            ).start()
            t0 = time.time()
            overrun_noted = False
            while not holder["done"].wait(0.2):
                ctl.poll_evidence()
                if not overrun_noted and time.time() - t0 > round_deadline_s:
                    overrun_noted = True
                    for peer in ctl.members:
                        if peer != member_id:
                            ctl.note_deadline_overrun(peer)
                if ctl.dead_peers():
                    return None  # abandon the wedge: recovery owns it now
            return holder

        partition_honored = False
        hold_t0 = None
        while r < rounds_total:
            ctl.poll_evidence()
            # DCN partition window (ChaosPolicy-scripted): the target
            # hushes its beats and stalls — the peer must ride out the
            # lone heartbeat lapse without degrading
            if (
                partition_target == member_id
                and not partition_honored
                and os.path.exists(os.path.join(mh_dir, "partition-pause.json"))
            ):
                rec = _wait_json(os.path.join(mh_dir, "partition-pause.json"))
                partition_honored = True
                hold_beats.set()
                time.sleep(float(rec["dur"]))
                hold_beats.clear()
                result["partition_honored_s"] = rec["dur"]
            # live JOIN absorption at a PLANNED boundary: the lowest rank
            # publishes the plan one boundary ahead so every (collective-
            # synchronized) member re-forms at the same round
            holding = (
                expect_joins
                and ctl.joins_absorbed < expect_joins
                and recovery_target is None
                and r >= max(rounds_total - join_reserve, 1)
            )
            if absorb and recovery_target is None:
                plan_path = os.path.join(mh_dir, f"absorb-plan-{ctl.epoch}.json")
                plan = _read_json(plan_path)
                pending_joins = ctl.pending_joins()
                if (
                    pending_joins
                    and member_id == ctl.members[0]
                    and (plan is None or plan["round"] < r)
                ):
                    # holding members all sit at THIS boundary, so absorb
                    # now; mid-schedule the plan lands one boundary ahead
                    # (collective lockstep means no member is past it yet)
                    plan = {"round": r if holding else r + 1,
                            "joiners": pending_joins}
                    _put_file(plan_path, json.dumps(plan))
                if (
                    plan is not None
                    and plan["round"] == r
                    and any(j not in ctl.members for j in plan["joiners"])
                ):
                    _commit_snapshot(r)
                    t0 = time.time()
                    world = ctl.absorb_joins(plan["joiners"])
                    _put_file(
                        os.path.join(mh_dir, f"resume-{ctl.epoch}.json"),
                        json.dumps({"round": r}),
                    )
                    g = _build(ctl.members)
                    _restore(g, ctl.members, only_progress=r)
                    world.sync("post-join")
                    pending_detach = True
                    _full_mask_check(r, f"post-join epoch {ctl.epoch}")
                    mask_know = _closure(r)
                    result["joins"].append(
                        {
                            "epoch": ctl.epoch,
                            "members": list(ctl.members),
                            "absorb_s": round(time.time() - t0, 2),
                        }
                    )
                    hold_t0 = None
                    continue
            dead = ctl.dead_peers()
            if dead:
                prev_members = list(ctl.members)
                survivors = [m for m in prev_members if m not in dead]
                t0 = time.time()
                ctl.degrade(f"evidence converged: {','.join(dead)}")
                # the counted degrade window: LOCAL serving continues
                # (eager, single-host) while the re-form ladder runs
                import jax

                local_ok = int(jax.jit(lambda a: a + 1)(np.arange(3))[2]) == 3
                world = ctl.reform(survivors)
                committed = [_progress(m) for m in prev_members]
                replay_from, replay_to = min(committed), max(committed)
                g = _build(ctl.members)
                restored = _restore(g, prev_members)
                world.sync("post-reform")
                pending_detach = world.is_multiprocess
                r = replay_from
                recovery_target = replay_to
                # the fleet plane's view of the kill: the victim's last
                # snapshot stays visible but MUST be marked stale (evicted
                # by membership), never silently merged (ISSUE 18)
                telem_agg.sync_board(board)
                for m in dead:
                    telem_agg.mark_evicted(m)
                telem_agg.note_members(ctl.members)
                not_stale = set(dead) - telem_agg.stale_hosts()
                if not_stale:
                    result["violations"].append(
                        f"mesh telemetry: dead host(s) {sorted(not_stale)} "
                        f"not marked stale after degrade"
                    )
                result["recoveries"].append(
                    {
                        "dead": dead,
                        "epoch": ctl.epoch,
                        "members": list(ctl.members),
                        "replay_from": replay_from,
                        "replay_to": replay_to,
                        "restored_shards": restored,
                        "local_serve_ok": local_ok,
                        "reform_s": round(time.time() - t0, 2),
                    }
                )
                if r >= recovery_target:
                    _stamp_recovery()
                continue
            if holding:
                # a smoke-scale schedule outruns a joiner's interpreter
                # start: hold the reserved boundary (still beating, still
                # polling evidence) until the scripted join is absorbed
                if hold_t0 is None:
                    hold_t0 = time.time()
                if time.time() - hold_t0 > join_hold_s:
                    result["violations"].append(
                        f"expected {expect_joins} joiner(s), "
                        f"{ctl.joins_absorbed} absorbed within {join_hold_s:.0f}s"
                    )
                    expect_joins = 0
                else:
                    time.sleep(0.2)
                continue
            holder = _dispatch_with_deadline(g, r)
            if holder is None:
                continue
            if holder["err"]:
                result["violations"].append(f"round {r}: {holder['err']}")
                break
            if recovery_target is None:
                _stage_check(r, holder["counts"])
            r += 1
            _commit_snapshot(r)
            if pending_detach:
                # all world members reach this barrier after committing
                # the SAME round (the collective kept them in lockstep)
                pending_detach = False
                if world.is_multiprocess:
                    ctl.detach()
            if recovery_target is not None and r >= recovery_target:
                _stamp_recovery()

        _full_mask_check(r, "phase end")
    except Exception as e:  # noqa: BLE001 — the gate reads violations, not a traceback
        result["violations"].append(f"elastic worker error: {e!r}")
    stop_beats.set()
    if divergence:
        result["violations"].append(f"{divergence} chain stage(s) diverged")
    try:
        telem_agg.sync_board(board)
        if ctl is not None:
            telem_agg.note_members(ctl.members)
        result["mesh_telemetry"] = telem_agg.summary()
    except Exception as e:  # noqa: BLE001 — telemetry must not mask the arc
        result["mesh_telemetry"] = {"error": repr(e)}
    result.update(
        rounds_committed=r,
        divergence=divergence,
        serving_ts=time.time(),
        controller=ctl.snapshot() if ctl is not None else None,
        events={
            k: events.count(k)
            for k in (
                "mesh_detached", "mesh_degraded", "mesh_evidence",
                "mesh_reform_attempt", "mesh_reform_failed", "mesh_reform_ok",
                "mesh_coordinator_takeover", "mesh_join_absorbed",
                "mesh_joined", "hier_fallback",
            )
        },
    )
    if g is not None:
        st = g.stats()
        result["stats"] = {
            k: st[k]
            for k in (
                "exchange", "hosts", "waves_run", "cross_host_words",
                "bucket_resizes", "hier_fallbacks",
                "exchange_async", "async_depth", "quiescence_checks",
                "spec_levels_total",
            )
        }
        if async_depth > 0 and g.quiescence_checks == 0:
            result["violations"].append(
                "async requested but zero quiescence checks ran (silent sync)"
            )
    with open(
        os.path.join(mh_dir, f"result_elastic_{member_id}.json"), "w"
    ) as f:
        json.dump(result, f)
    # detach already retired the agent; drop any service/backends so the
    # process exits clean (no jax.distributed.shutdown on a gone world)
    teardown_world(rebuild_local=False)
    return 0 if not result["violations"] else 1


# ================================================================ orchestrator
def _launch(phase: str, n_hosts: int, dph: int, mh_dir: str, extra_env: dict):
    from stl_fusion_tpu.cluster.multihost import launch_hosts

    env = dict(os.environ)
    env.update(
        MESH_MH_PHASE=phase,
        MESH_MH_DIR=mh_dir,
        **{k: str(v) for k, v in extra_env.items()},
    )
    return launch_hosts(
        [sys.executable, os.path.abspath(__file__), "--worker"],
        n_hosts=n_hosts,
        devices_per_host=dph,
        env=env,
    )


def _read_results(mh_dir: str, phase: str, n_hosts: int) -> list:
    out = []
    for h in range(n_hosts):
        path = os.path.join(mh_dir, f"result_{phase}_h{h}.json")
        if os.path.exists(path):
            with open(path) as f:
                out.append(json.load(f))
    return out


def run_multihost(out: dict) -> None:
    """The multihost record section + gates, merged into a mesh_path-style
    ``out`` dict (``out["violations"]`` drives the exit code)."""
    n_hosts = _env_int("MESH_MULTIHOST", 2)
    dph = _env_int("MESH_MH_DPH", 2)
    n = _env_int("MESH_MH_NODES", 40_000)
    rounds = _env_int("MESH_MH_ROUNDS", 4)
    timeout_s = _env_int("MESH_MH_TIMEOUT", 600)
    members = [f"h{i}" for i in range(n_hosts)]
    mh: dict = {"hosts": n_hosts, "devices_per_host": dph, "nodes": n}
    out["multihost"] = mh
    base_env = {
        "MESH_MH_MEMBERS": ",".join(members),
        "MESH_MH_NODES": n,
        "MESH_MH_ROUNDS": rounds,
    }

    def _wait(procs, what: str) -> list:
        rcs = []
        deadline = time.time() + timeout_s
        for p in procs:
            try:
                rcs.append(p.wait(timeout=max(deadline - time.time(), 1)))
            except subprocess.TimeoutExpired:
                p.kill()
                rcs.append(-9)
                out["violations"].append(f"{what}: host timed out")
        return rcs

    with tempfile.TemporaryDirectory(prefix="fusion-mh-") as mh_dir:
        # ---- scale leg (oracle + resize + DCN) ----
        if os.environ.get("MESH_MH_SCALE", "1") == "1":
            log(f"multihost scale leg: {n_hosts} hosts x {dph} devices, {n} nodes")
            t0 = time.time()
            procs = _launch("scale", n_hosts, dph, mh_dir, base_env)
            rcs = _wait(procs, "scale")
            results = _read_results(mh_dir, "scale", n_hosts)
            if len(results) < n_hosts or any(r != 0 for r in rcs):
                out["violations"].append(
                    f"scale leg: rcs={rcs}, results={len(results)}/{n_hosts}"
                )
            for r in results:
                out["violations"].extend(
                    f"scale h{r['host']}: {v}" for v in r.get("violations", [])
                )
            # key by the host id each worker wrote — _read_results skips
            # missing files, so results[0] is not necessarily host 0
            h0 = next((r for r in results if r.get("host") == 0), {})
            mh["scale"] = {
                "wall_s": round(time.time() - t0, 1),
                "oracle_exact": h0.get("oracle_exact"),
                "inv_per_s": h0.get("inv_per_s"),
                "burst_s": h0.get("burst_s"),
                "build_s": h0.get("build_s"),
                "stats": h0.get("stats"),
                "resize": h0.get("resize"),
                "dcn": h0.get("dcn") or {},
                "mesh_telemetry": h0.get("mesh_telemetry"),
                "health": h0.get("health"),
                "hotkeys": h0.get("hotkeys"),
                "trace": compact_trace(h0.get("trace")),
            }
            if not (h0.get("trace") or {}).get("levels"):
                out["violations"].append("scale: stitched wave timeline is empty")
            if (h0.get("mesh_telemetry") or {}).get("stale"):
                out["violations"].append("scale: live host marked stale in merge")
            if (h0.get("health") or {}).get("verdict") != "ok":
                out["violations"].append(
                    f"scale: mesh health verdict {(h0.get('health') or {}).get('verdict')!r}"
                )
            dcn0 = h0.get("dcn") or {}
            if not dcn0.get("dcn_fallback_relays"):
                out["violations"].append("DCN fallback not exercised cross-process")
            if not dcn0.get("client_observed_fence"):
                out["violations"].append("DCN fence never reached the peer host")
            if dcn0.get("mesh_member_relays"):
                out["violations"].append(
                    f"{dcn0['mesh_member_relays']} on-mesh member relay(s)"
                )
            # single-process routed oracle cross-check (the acceptance
            # criterion: 2-process wave 0 == 1-process wave 0 == BFS)
            if os.environ.get("MESH_MH_XCHECK", "1") == "1":
                mh["scale"]["xcheck"] = _single_process_xcheck(mh_dir, n, out)

        # ---- elastic chaos ladder (ISSUE 16): kill+flap, join, partition ----
        if os.environ.get("MESH_MH_ELASTIC", "1") == "1" and n_hosts >= 2:
            _elastic_leg(dph, mh_dir, base_env, members, out, mh, _wait)
        if os.environ.get("MESH_MH_JOIN3", "1") == "1":
            _join_leg(dph, mh_dir, base_env, out, mh, _wait)
        if os.environ.get("MESH_MH_PARTITION", "1") == "1":
            _partition_leg(dph, mh_dir, base_env, out, mh, _wait)
        # ---- geometry certify: hier past 2 hosts, non-pow2 fallback ----
        for spec in os.environ.get("MESH_MH_GEOMETRIES", "4,3").split(","):
            if spec.strip():
                _geometry_leg(int(spec), dph, mh_dir, base_env, out, mh, _wait)


def _single_process_xcheck(mh_dir: str, n: int, out: dict) -> dict:
    """Rebuild the same graph on THIS process's local device pool and
    compare wave-0 masks bit-for-bit with the 2-process run."""
    from stl_fusion_tpu.cluster import DevicePlacement, ShardMap
    from stl_fusion_tpu.graph.synthetic import power_law_dag
    from stl_fusion_tpu.parallel import RoutedShardedGraph, graph_mesh

    mask_path = os.path.join(mh_dir, "wave_mask.npy")
    if not os.path.exists(mask_path):
        out["violations"].append("xcheck: worker exported no wave mask")
        return {"ok": False}
    packed = np.load(mask_path)
    theirs = np.unpackbits(packed)[:n].astype(bool)
    src, dst = power_law_dag(n, avg_degree=3.0, seed=7)
    members = os.environ.get("MESH_MH_MEMBERS", "h0,h1").split(",")
    smap = ShardMap.initial(members, n_shards=_env_int("MESH_MH_SHARDS", 64))
    mesh = graph_mesh()
    pl = DevicePlacement.build(smap, mesh.devices.size, n)
    g = RoutedShardedGraph(src, dst, n, pl, mesh=mesh, exchange="a2a")
    schedule = round_seeds(
        123, n, _env_int("MESH_MH_ROUNDS", 4),
        _env_int("MESH_MH_SEEDS_PER_ROUND", 4), _env_int("MESH_MH_STAGES", 2),
    )
    pending = g.dispatch_union_chain(schedule[0])
    g.harvest_union_chain(pending)
    for r in schedule[1:]:
        g.harvest_union_chain(g.dispatch_union_chain(r))
    mine = g.invalid_mask()
    ok = bool(np.array_equal(mine, theirs))
    if not ok:
        out["violations"].append(
            f"xcheck: multi-process mask != single-process routed oracle "
            f"({int((mine != theirs).sum())} nodes)"
        )
    return {"ok": ok, "single_process_devices": int(mesh.devices.size)}


def _wait_cond(cond, timeout_s: float, what: str, out: dict) -> bool:
    t0 = time.time()
    while time.time() - t0 < timeout_s:
        if cond():
            return True
        time.sleep(0.1)
    out["violations"].append(f"{what}: timed out after {timeout_s:.0f}s")
    return False


def _elastic_leg(dph, root_dir, base_env, members, out, mh, _wait):
    """Host-kill + flap rung: SIGKILL h1 mid-burst (ChaosPolicy-scripted),
    the SAME h0 process degrades/re-forms/recovers under the budget, then
    h1 relaunches as a live JOINER and is absorbed — zero divergent
    waves, survivor never restarted (one Popen serves the whole arc)."""
    from stl_fusion_tpu.cluster.mesh_controller import RendezvousBoard
    from stl_fusion_tpu.resilience.chaos import SCENARIOS

    kill_policy = SCENARIOS["host_kill_reform"]()
    flap_policy = SCENARIOS["host_flap"]()
    leg_dir = os.path.join(root_dir, "elastic")
    os.makedirs(leg_dir, exist_ok=True)
    rounds = max(_env_int("MESH_MH_ROUNDS", 4) + 2, 6)
    budget = float(os.environ.get("MESH_MH_RECOVERY_BUDGET_S", "15"))
    timeout_s = _env_int("MESH_MH_TIMEOUT", 600)
    env = dict(
        base_env,
        MESH_MH_ROUNDS=rounds,
        MESH_MH_ROUND_DEADLINE=os.environ.get("MESH_MH_ROUND_DEADLINE", "6"),
        MESH_MH_EXPECT_JOINS=1,  # members hold the last rounds for the flap rejoin
    )
    log(f"elastic leg: kill {members[1]} mid-burst, in-process recovery, flap rejoin")
    procs = _launch("elastic", 2, dph, leg_dir, env)

    def _prog(m: str) -> int:
        try:
            with open(os.path.join(leg_dir, f"progress_{m}")) as f:
                return int(f.read() or 0)
        except OSError:
            return 0

    # kill only once BOTH hosts run detached (the agent's shutdown barrier
    # must not be mid-flight) and the victim has committed detached rounds
    ready = _wait_cond(
        lambda: all(
            os.path.exists(os.path.join(leg_dir, f"detached-{m}"))
            for m in members[:2]
        )
        and _prog(members[1]) >= 2
        and procs[1].poll() is None,
        timeout_s, "elastic: kill point", out,
    )
    if not ready:
        for p in procs:
            p.kill()
        return
    assert kill_policy.peer_kills, "host_kill_reform script names no victim"
    victim = members[1]
    procs[1].kill()
    t_kill = time.time()
    # the orchestrator that SIGKILLed the victim says so — the
    # authoritative evidence signal (lapse + overrun converge without it)
    RendezvousBoard(os.path.join(leg_dir, "board")).flag_dead(
        victim, "sigkill by chaos driver"
    )
    # flap rung: the host_flap script's second kill offset is the fast-
    # rejoin delay — relaunch the victim as a live JOINER while the
    # survivor is still mid-recovery (its breaker window still open)
    flap_delay = (
        flap_policy.peer_kills[1][0] - flap_policy.peer_kills[0][0]
    ) * 10.0
    time.sleep(max(flap_delay, 0.5))
    t_rejoin = time.time()
    jprocs = _launch(
        "elastic", 1, dph, leg_dir,
        dict(env, MESH_MH_JOINER=1, MESH_MH_MEMBER_ID=victim,
             MESH_MH_JOIN_TIMEOUT=timeout_s),
    )
    rcs = _wait([procs[0]] + jprocs, "elastic")
    results = {
        m: _read_json(os.path.join(leg_dir, f"result_elastic_{m}.json"))
        for m in members[:2]
    }
    for m, res in results.items():
        if res is None:
            out["violations"].append(f"elastic: no result from {m}")
        else:
            out["violations"].extend(
                f"elastic {m}: {v}" for v in res.get("violations", [])
            )
    if any(rc != 0 for rc in rcs):
        out["violations"].append(f"elastic: nonzero exits {rcs}")
    h0 = results.get(members[0]) or {}
    rec = _read_json(os.path.join(leg_dir, f"recovered-{members[0]}"))
    recovery_s = None
    if rec is None:
        out["violations"].append("elastic: survivor never stamped a recovery")
    else:
        recovery_s = round(rec["ts"] - t_kill, 2)
        if not rec.get("oracle_exact"):
            out["violations"].append("elastic: recovery wave not oracle-exact")
        if recovery_s > budget:
            out["violations"].append(
                f"elastic: host_kill_recovery_s {recovery_s} > budget {budget}"
            )
    if not h0.get("recoveries"):
        out["violations"].append("elastic: survivor recorded no recovery arc")
    if not (h0.get("events") or {}).get("mesh_degraded"):
        out["violations"].append("elastic: degrade window was not counted")
    if not h0.get("joins"):
        out["violations"].append("elastic: flap joiner never absorbed")
    reb = _read_json(os.path.join(leg_dir, f"rebalanced-{victim}"))
    if reb is None or not reb.get("oracle_exact"):
        out["violations"].append("elastic: flap rejoin not oracle-exact")
    mh["elastic"] = {
        "killed_host": victim,
        "host_kill_recovery_s": recovery_s,
        "recovery_budget_s": budget,
        "survivor_restarts": 0,  # structural: ONE Popen serves the whole arc
        "survivor_epoch": (h0.get("controller") or {}).get("epoch"),
        "recoveries": h0.get("recoveries"),
        "joins": h0.get("joins"),
        "flap_rejoin_s": round(reb["ts"] - t_rejoin, 2) if reb else None,
        "divergence": [(res or {}).get("divergence") for res in results.values()],
        "events": h0.get("events"),
        "mesh_telemetry": h0.get("mesh_telemetry"),
    }


def _join_leg(dph, root_dir, base_env, out, mh, _wait):
    """Live JOIN leg: a serving 2-host mesh absorbs h2 — re-form to 3
    hosts (non-power-of-2: hier resolves via the counted gather
    fallback), boundary snapshots rebalance, join-to-rebalanced gated."""
    leg_dir = os.path.join(root_dir, "join3")
    os.makedirs(leg_dir, exist_ok=True)
    members = ["h0", "h1", "h2"]
    rounds = max(_env_int("MESH_MH_ROUNDS", 4) + 2, 6)
    budget = float(os.environ.get("MESH_MH_JOIN_BUDGET_S", "30"))
    timeout_s = _env_int("MESH_MH_TIMEOUT", 600)
    env = dict(
        base_env,
        MESH_MH_MEMBERS=",".join(members),
        MESH_MH_ROUNDS=rounds,
        MESH_MH_EXPECT_JOINS=1,  # members hold the last rounds for h2
    )
    log("join leg: live 2 -> 3 hosts (non-pow2 gather fallback, counted)")
    procs = _launch("elastic", 2, dph, leg_dir, env)
    ready = _wait_cond(
        lambda: all(
            os.path.exists(os.path.join(leg_dir, f"detached-{m}"))
            for m in members[:2]
        ),
        timeout_s, "join3: detach point", out,
    )
    if not ready:
        for p in procs:
            p.kill()
        return
    t_join = time.time()
    jprocs = _launch(
        "elastic", 1, dph, leg_dir,
        dict(env, MESH_MH_JOINER=1, MESH_MH_MEMBER_ID="h2",
             MESH_MH_JOIN_TIMEOUT=timeout_s),
    )
    rcs = _wait(procs + jprocs, "join3")
    results = {
        m: _read_json(os.path.join(leg_dir, f"result_elastic_{m}.json"))
        for m in members
    }
    for m, res in results.items():
        if res is None:
            out["violations"].append(f"join3: no result from {m}")
        else:
            out["violations"].extend(
                f"join3 {m}: {v}" for v in res.get("violations", [])
            )
    if any(rc != 0 for rc in rcs):
        out["violations"].append(f"join3: nonzero exits {rcs}")
    h0 = results.get("h0") or {}
    if not h0.get("joins"):
        out["violations"].append("join3: members absorbed no joiner")
    reb = _read_json(os.path.join(leg_dir, "rebalanced-h2"))
    join_s = None
    if reb is None or not reb.get("oracle_exact"):
        out["violations"].append("join3: joiner warm start not oracle-exact")
    else:
        join_s = round(reb["ts"] - t_join, 2)
        if join_s > budget:
            out["violations"].append(
                f"join3: join_to_rebalanced_s {join_s} > budget {budget}"
            )
    st = h0.get("stats") or {}
    if os.environ.get("MESH_MH_EXCHANGE", "hier") == "hier" and (
        st.get("exchange") != "gather" or not st.get("hier_fallbacks")
    ):
        out["violations"].append(
            f"join3: non-pow2 gather fallback not counted ({st})"
        )
    mh["join3"] = {
        "join_to_rebalanced_s": join_s,
        "join_budget_s": budget,
        "final_members": (h0.get("controller") or {}).get("members"),
        "joins": h0.get("joins"),
        "exchange_after_join": st.get("exchange"),
        "hier_fallbacks": st.get("hier_fallbacks"),
        "divergence": [(res or {}).get("divergence") for res in results.values()],
    }


def _partition_leg(dph, root_dir, base_env, out, mh, _wait):
    """DCN-partition ride-through: the mesh_partition ChaosPolicy window
    silences h1's beats mid-leg; h0 must observe the lapse (counted
    evidence) and NOT degrade — single-signal eviction is the bug this
    leg pins."""
    from stl_fusion_tpu.resilience.chaos import SCENARIOS

    policy = SCENARIOS["mesh_partition"]()
    dur = round(policy.partitions[0][1] * 2.0, 1)  # scripted window -> wall time
    leg_dir = os.path.join(root_dir, "partition")
    os.makedirs(leg_dir, exist_ok=True)
    timeout_s = _env_int("MESH_MH_TIMEOUT", 600)
    env = dict(
        base_env,
        MESH_MH_ROUNDS=6,
        MESH_MH_ROUND_DEADLINE=45,
        MESH_MH_HB_TIMEOUT=1.0,
        MESH_MH_ABSORB=0,
        MESH_MH_PARTITION_TARGET="h1",
    )
    log(f"partition leg: {dur}s beat blackout on h1 — must ride through")
    procs = _launch("elastic", 2, dph, leg_dir, env)
    ready = _wait_cond(
        lambda: all(
            os.path.exists(os.path.join(leg_dir, f"detached-h{i}"))
            for i in range(2)
        ),
        timeout_s, "partition: detach point", out,
    )
    if not ready:
        for p in procs:
            p.kill()
        return
    _put_file(
        os.path.join(leg_dir, "partition-pause.json"),
        json.dumps({"member": "h1", "dur": dur}),
    )
    rcs = _wait(procs, "partition")
    results = {
        f"h{i}": _read_json(os.path.join(leg_dir, f"result_elastic_h{i}.json"))
        for i in range(2)
    }
    for m, res in results.items():
        if res is None:
            out["violations"].append(f"partition: no result from {m}")
        else:
            out["violations"].extend(
                f"partition {m}: {v}" for v in res.get("violations", [])
            )
    if any(rc != 0 for rc in rcs):
        out["violations"].append(f"partition: nonzero exits {rcs}")
    h0 = results.get("h0") or {}
    h1 = results.get("h1") or {}
    ctl0 = h0.get("controller") or {}
    ev_h1 = (ctl0.get("evidence") or {}).get("h1") or {}
    if ctl0.get("degrades"):
        out["violations"].append("partition: degraded on a lone lapse")
    if "heartbeat_lapse" not in (ev_h1.get("kinds") or {}):
        out["violations"].append("partition: lapse evidence never observed")
    if "partition_honored_s" not in h1:
        out["violations"].append("partition: target never honored the window")
    mh["partition"] = {
        "window_s": dur,
        "degrades": ctl0.get("degrades"),
        "evidence_score": ev_h1.get("score"),
        "evidence_kinds": sorted((ev_h1.get("kinds") or {})),
        "divergence": [(res or {}).get("divergence") for res in results.values()],
    }


def _geometry_leg(hosts, dph, root_dir, base_env, out, mh, _wait):
    """Geometry certify: the scale oracle at ``hosts`` emulated hosts —
    pow2 counts certify the hierarchical exchange proper; non-pow2 counts
    certify the counted gather fallback (exact, never a decline)."""
    leg_dir = os.path.join(root_dir, f"geom{hosts}")
    os.makedirs(leg_dir, exist_ok=True)
    members = [f"h{i}" for i in range(hosts)]
    n = min(_env_int("MESH_MH_NODES", 40_000), _env_int("MESH_MH_GEOM_NODES", 12_000))
    env = dict(
        base_env,
        MESH_MH_MEMBERS=",".join(members),
        MESH_MH_NODES=n,
        MESH_MH_ROUNDS=2,
        MESH_MH_RESIZE=0,
        MESH_MH_DCN=0,
    )
    log(f"geometry certify: {hosts} hosts x {dph} devices, {n} nodes")
    t0 = time.time()
    procs = _launch("scale", hosts, dph, leg_dir, env)
    rcs = _wait(procs, f"geom{hosts}")
    results = _read_results(leg_dir, "scale", hosts)
    if len(results) < hosts or any(rc != 0 for rc in rcs):
        out["violations"].append(
            f"geom{hosts}: rcs={rcs}, results={len(results)}/{hosts}"
        )
    for res in results:
        out["violations"].extend(
            f"geom{hosts} h{res['host']}: {v}" for v in res.get("violations", [])
        )
    h0 = next((res for res in results if res.get("host") == 0), {})
    st = h0.get("stats") or {}
    pow2 = hosts & (hosts - 1) == 0
    if os.environ.get("MESH_MH_EXCHANGE", "hier") == "hier":
        if pow2 and (st.get("exchange") != "hier" or st.get("hier_fallbacks")):
            out["violations"].append(
                f"geom{hosts}: pow2 geometry lost the hier exchange ({st})"
            )
        if not pow2 and (
            st.get("exchange") != "gather" or st.get("hier_fallbacks") != 1
        ):
            out["violations"].append(
                f"geom{hosts}: non-pow2 fallback not counted ({st})"
            )
    # async certify: when the ladder runs at FUSION_MH_ASYNC_DEPTH > 0
    # this geometry must have actually speculated (quiescence checks are
    # the counted evidence — zero means a silent downgrade to sync)
    if _env_int("FUSION_MH_ASYNC_DEPTH", 0) > 0 and not st.get(
        "quiescence_checks"
    ):
        out["violations"].append(
            f"geom{hosts}: async requested but never certified ({st})"
        )
    mh.setdefault("geometry", {})[str(hosts)] = {
        "hosts": hosts,
        "nodes": n,
        "wall_s": round(time.time() - t0, 1),
        "oracle_exact": h0.get("oracle_exact"),
        "inv_per_s": h0.get("inv_per_s"),
        "exchange": st.get("exchange"),
        "hier_fallbacks": st.get("hier_fallbacks"),
        "cross_host_words": st.get("cross_host_words"),
        "exchange_async": st.get("exchange_async"),
        "async_depth": st.get("async_depth"),
        "quiescence_checks": st.get("quiescence_checks"),
        "trace_levels": len((h0.get("trace") or {}).get("levels") or ()),
        "telemetry_hosts": (h0.get("mesh_telemetry") or {}).get("hosts"),
    }


def main() -> None:
    if "--worker" in sys.argv:
        if os.environ.get("MESH_MH_PHASE") == "elastic":
            sys.exit(run_elastic_worker())
        sys.exit(run_worker())
    # the hosts this harness spawns are pinned to the CPU backend
    # (cluster/multihost.py::host_env); the parent only orchestrates
    from stl_fusion_tpu.graph import require_accelerator

    out: dict = {
        **require_accelerator("perf/mesh_multihost.py"), "violations": [],
    }
    run_multihost(out)
    ok = not out["violations"]
    out["ok"] = ok
    print("# full record: " + json.dumps(out), file=sys.stderr, flush=True)
    print(json.dumps(out, separators=(",", ":")))
    if not ok:
        log(f"GATE FAILURES: {out['violations']}")
        sys.exit(1)


if __name__ == "__main__":
    main()
