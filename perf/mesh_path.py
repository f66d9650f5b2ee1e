#!/usr/bin/env python
"""Mesh-sharded device graph measurement + gate (ISSUE 9).

Two legs, one JSON line on stdout (full record on stderr):

1. **North-star static leg** — a power-law graph of ``MESH_NODES``
   (default 80M: ≥8x the single-device 10M BASELINE scenario, targeting
   the ROADMAP 100M) built as cluster-routed CSR shards spanning ALL mesh
   devices (cluster/placement.py -> parallel/routed_wave.py), sustaining
   ``MESH_WAVES`` cascading-invalidation waves whose cross-shard
   frontiers resolve via collectives (``MESH_EXCHANGE``: a2a bucket
   routing by default). Wave 0 is ORACLE-CHECKED against a vectorized
   host BFS (exact mask equality) — at any scale, every run.

2. **Live smoke leg** (``MESH_LIVE_NODES``, default 20K) — a real hub +
   TpuGraphBackend with ``enable_mesh_routing``: the nonblocking
   WavePipeline dispatches fused chains THROUGH the routed mesh path,
   a mid-burst reshard (kill one member) MOVES device shards with
   zero oracle-divergent reads, and the fan-out's member-relay scope
   proves the frontier never re-entered through per-key host RPC.

GATES (exit 1 — the tier1 mesh smoke rides them):
- wave 0 oracle divergence, or any reshard-raced wave divergence;
- the pipeline fell back to eager per-wave dispatch (``eager_waves > 0``)
  or never fused (``fused_dispatches == 0``);
- ``fusion_mesh_routed_waves_total == 0`` (mesh path disengaged);
- ``mesh_member_relays > 0`` (a frontier surfaced to the host relay for
  an on-mesh member — the exact regression ISSUE 9 retires);
- a reshard that moved zero device shards.

3. **Multihost leg** (``MESH_MULTIHOST>=2`` — ISSUE 15): delegates to
   perf/mesh_multihost.py — 2+ REAL OS-process hosts joined by
   ``jax.distributed`` + gloo run the hierarchical exchange, with the
   wave mask cross-checked against THIS process's single-process routed
   oracle, a counted in-place bucket resize under live patching, a DCN
   fence over a real TCP socket between the host processes, and the
   host-kill → survivor → warm-rejoin chaos ladder. Its violations merge
   into this script's gate (exit 1).

4. **Async A/B leg** (``MESH_ASYNC=1`` — ISSUE 17): the same graph and
   seed schedule run through a bulk-synchronous routed graph AND an
   async one (``MESH_ASYNC_DEPTH`` speculative levels between merges).
   Gates (exit 1): any per-wave mask divergence — async vs sync vs host
   BFS, all three bit-identical; ``quiescence_checks == 0`` on the async
   graph (the uncounted-fallback-to-sync tell); zero reclaimed exchange
   barriers (async merge epochs must be STRICTLY fewer than sync levels
   — the structural, noise-free form of the stall reclaim). The
   wall-clock delta feeds the ``fusion_mesh_level_stall_ms`` gauge.
   ``MESH_ASYNC=1`` also switches the live leg's routed mirror to async
   so the superround/pipeline composition rides the same mode.

Env: MESH_NODES, MESH_WAVES (2), MESH_SEEDS (100_000), MESH_EXCHANGE
(a2a; the live leg rides it too — "hier" + MESH_HOSTS emulates the host
axis in-process), MESH_HOSTS (1), MESH_LIVE_NODES (20_000), MESH_MEMBERS
(4), MESH_SHARDS (256), MESH_SKIP_STATIC=1 (smoke: live leg only),
MESH_SKIP_LIVE=1, MESH_MULTIHOST (0) + the
MESH_MH_* knobs of perf/mesh_multihost.py, MESH_ASYNC (0),
MESH_ASYNC_DEPTH (4), MESH_AB_NODES (120_000), MESH_AB_WAVES (3),
MESH_AB_SEEDS (64).
"""
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def numpy_bfs_mask(src, dst, n, seeds):
    """Vectorized host BFS closure — the oracle at any scale (a Python
    set-BFS at 80M nodes would dominate the run)."""
    inv = np.zeros(n, dtype=bool)
    inv[np.asarray(seeds, dtype=np.int64)] = True
    frontier = inv.copy()
    while frontier.any():
        fire = frontier[src]
        nxt = np.zeros(n, dtype=bool)
        nxt[dst[fire]] = True
        nxt &= ~inv
        inv |= nxt
        frontier = nxt
    return inv


def compact_trace(stitched) -> dict:
    """A record-sized digest of one stitched wave timeline (ISSUE 18):
    the per-level segments stay in the trace store / ``GET /trace`` — the
    perf record carries the straggler table and the pacing verdict."""
    if not stitched:
        return None
    return {
        "cause": stitched["cause"],
        "hosts": stitched["hosts"],
        "partial": stitched["partial"],
        "duration_ms": stitched["duration_ms"],
        "segments": len(stitched["segments"]),
        "levels": len(stitched["levels"]),
        "straggler": stitched["straggler"][:4],
        "paced_by": stitched["paced_by"],
    }


def check_shard_devices(graph, mesh, out: dict, leg: str) -> list:
    """Every resident array of a routed graph must have block ``d`` on
    ``mesh.devices.flat[d]``, the device DevicePlacement assigns it: all
    of it on device 0 would still give right answers, so it is a gate."""
    want = [d.id for d in mesh.devices.flat]
    layout = graph.device_layout()
    log(f"{leg}: mesh devices {want}; array -> shard devices {layout}")
    wrong = sorted(name for name, got in layout.items() if got != want)
    if wrong:
        out["violations"].append(
            f"{leg}: arrays not laid out over the mesh devices: {wrong}"
        )
    return want


def run_static(mesh, out: dict) -> None:
    from stl_fusion_tpu.cluster import DevicePlacement, ShardMap
    from stl_fusion_tpu.graph.synthetic import power_law_dag
    from stl_fusion_tpu.parallel import RoutedShardedGraph

    n = int(os.environ.get("MESH_NODES", 80_000_000))
    n_waves = int(os.environ.get("MESH_WAVES", 2))
    n_seeds = int(os.environ.get("MESH_SEEDS", 100_000))
    exchange = os.environ.get("MESH_EXCHANGE", "a2a")
    n_members = int(os.environ.get("MESH_MEMBERS", 4))
    n_shards = int(os.environ.get("MESH_SHARDS", 256))

    t0 = time.time()
    src, dst = power_law_dag(n, avg_degree=3.0, seed=7)
    gen_s = time.time() - t0
    log(f"static: {n} nodes, {len(src)} edges generated in {gen_s:.1f}s")
    smap = ShardMap.initial([f"m{i}" for i in range(n_members)], n_shards=n_shards)
    t0 = time.time()
    placement = DevicePlacement.build(smap, mesh.devices.size, n)
    graph = RoutedShardedGraph(src, dst, n, placement, mesh=mesh, exchange=exchange)
    build_s = time.time() - t0
    log(f"static: routed shards built in {build_s:.1f}s "
        f"(e_cap {graph.e_cap}, bucket_cap {graph.bucket_cap})")
    shard_devices = check_shard_devices(graph, mesh, out, "static")

    rng = np.random.default_rng(123)
    seed_sets = [
        rng.choice(n, size=n_seeds, replace=False) for _ in range(n_waves)
    ]
    # compile (untimed), then the timed churn-model run: graph re-consistent
    # between waves, every wave cascades (the bench convention)
    t0 = time.time()
    c0, _ids, over0 = graph.run_wave_collect(seed_sets[0].tolist())
    compile_s = time.time() - t0
    graph.clear_invalid()
    totals, wave_s = [], []
    levels0 = graph.levels_total
    t_run = time.time()
    for w in range(n_waves):
        t0 = time.time()
        c, _ids, _over = graph.run_wave_collect(seed_sets[w].tolist())
        wave_s.append(time.time() - t0)
        totals.append(c)
        if w == 0:
            mask = graph.invalid_mask()
        graph.clear_invalid()
    elapsed = time.time() - t_run
    levels = graph.levels_total - levels0

    log("static: oracle BFS (vectorized host) for wave 0...")
    t0 = time.time()
    want = numpy_bfs_mask(src, dst, n, seed_sets[0])
    oracle_s = time.time() - t0
    oracle_exact = bool(np.array_equal(mask, want))
    if not oracle_exact:
        diff = int((mask != want).sum())
        log(f"GATE FAIL: wave 0 diverged from host BFS at {diff} node(s)")
        out["violations"].append(f"static oracle divergence ({diff} nodes)")
    total = int(sum(totals))
    out["static"] = {
        "nodes": n,
        "edges": int(len(src)),
        "mesh_devices": int(mesh.devices.size),
        "shard_devices": shard_devices,
        "members": n_members,
        "shards": n_shards,
        "exchange": exchange,
        "waves": n_waves,
        "seeds_per_wave": n_seeds,
        "total_invalidated": total,
        "inv_per_s": round(total / max(elapsed, 1e-9), 1),
        "wave_s": [round(t, 2) for t in wave_s],
        "exchange_levels": int(levels),
        "oracle_exact": oracle_exact,
        "oracle_s": round(oracle_s, 1),
        "build_s": round(build_s, 1),
        "compile_s": round(compile_s, 1),
        "gen_s": round(gen_s, 1),
        "vs_single_device_10m": round(n / 10_000_000, 1),
    }


def run_async_ab(mesh, out: dict) -> None:
    """ISSUE 17 A/B: one graph, one seed schedule, two routed builds —
    bulk-synchronous and async (``MESH_ASYNC_DEPTH`` speculative levels
    between global merges). The async run must converge to the
    BIT-IDENTICAL invalid mask on every wave while retiring strictly
    fewer exchange barriers; the reclaimed wall-clock (an honest delta of
    the two timed bursts, floored at zero — CPU emulation can make the
    speculation overhead exceed the collective savings at smoke scale)
    feeds the ``fusion_mesh_level_stall_ms`` MAX-gauge."""
    from stl_fusion_tpu.cluster import DevicePlacement, ShardMap
    from stl_fusion_tpu.graph.synthetic import power_law_dag
    from stl_fusion_tpu.parallel import RoutedShardedGraph
    from stl_fusion_tpu.parallel.routed_wave import record_level_stall_ms

    n = int(os.environ.get("MESH_AB_NODES", 120_000))
    n_waves = int(os.environ.get("MESH_AB_WAVES", 3))
    n_seeds = int(os.environ.get("MESH_AB_SEEDS", 64))
    depth = int(os.environ.get("MESH_ASYNC_DEPTH", 4))
    exchange = os.environ.get("MESH_EXCHANGE", "a2a")

    src, dst = power_law_dag(n, avg_degree=3.0, seed=11)
    smap = ShardMap.initial([f"m{i}" for i in range(4)], n_shards=64)
    placement = DevicePlacement.build(smap, mesh.devices.size, n)
    rng = np.random.default_rng(321)
    seed_sets = [
        rng.choice(n, size=n_seeds, replace=False).tolist()
        for _ in range(n_waves)
    ]

    def _burst(async_mode: bool):
        g = RoutedShardedGraph(
            src, dst, n, placement, mesh=mesh, exchange=exchange,
            exchange_async=async_mode, async_depth=depth,
        )
        g.run_wave_collect(seed_sets[0])  # compile (untimed)
        g.clear_invalid()
        levels0 = g.levels_total
        masks, totals = [], 0
        t0 = time.time()
        for s in seed_sets:
            c, _ids, _over = g.run_wave_collect(s)
            totals += int(c)
            masks.append(g.invalid_mask())
            g.clear_invalid()
        wall = time.time() - t0
        return g, masks, totals, g.levels_total - levels0, wall

    log(f"async A/B: {n} nodes, {n_waves} waves, depth {depth} ({exchange})")
    g_sync, m_sync, tot_sync, lv_sync, wall_sync = _burst(False)
    g_async, m_async, tot_async, lv_async, wall_async = _burst(True)

    divergence = 0
    for w, (a, s) in enumerate(zip(m_async, m_sync)):
        want = numpy_bfs_mask(src, dst, n, seed_sets[w])
        if not np.array_equal(a, s):
            divergence += 1
            out["violations"].append(
                f"async wave {w} diverged from sync at "
                f"{int((a != s).sum())} node(s)"
            )
        elif not np.array_equal(a, want):
            divergence += 1
            out["violations"].append(
                f"async wave {w} diverged from host BFS at "
                f"{int((a != want).sum())} node(s)"
            )
    if g_async.quiescence_checks == 0:
        out["violations"].append(
            "async graph ran zero quiescence checks (uncounted fallback "
            "to sync)"
        )
    reclaimed = lv_sync - lv_async
    if reclaimed <= 0:
        out["violations"].append(
            f"async reclaimed zero exchange barriers "
            f"(sync {lv_sync} vs async {lv_async} merge epochs)"
        )
    stall_ms = max(wall_sync - wall_async, 0.0) * 1e3
    # the cause rides into the reclaim histogram's exemplar ring: the
    # stall number links to the async leg's last stitched wave (ISSUE 19)
    record_level_stall_ms(stall_ms, cause=g_async.last_trace_cause)
    # the async burst's LAST wave, stitched: single-host here, but the
    # derived per-level segments + straggler table must exist (the
    # multihost leg stitches the same machinery across real processes)
    from stl_fusion_tpu.diagnostics.mesh_telemetry import global_mesh_trace

    stitched = (
        global_mesh_trace().stitch(g_async.last_trace_cause)
        if g_async.last_trace_cause
        else None
    )
    if stitched is None or not stitched["levels"]:
        out["violations"].append(
            "async A/B recorded no stitched wave timeline (trace hooks dark)"
        )
    out["async_ab"] = {
        "nodes": n,
        "waves": n_waves,
        "async_depth": depth,
        "exchange": exchange,
        "oracle_exact": divergence == 0,
        "sync_levels": lv_sync,
        "async_merge_epochs": lv_async,
        "levels_reclaimed": reclaimed,
        "quiescence_checks": g_async.quiescence_checks,
        "spec_levels_total": g_async.spec_levels_total,
        "level_stall_ms": round(stall_ms, 2),
        "sync_wall_s": round(wall_sync, 3),
        "async_wall_s": round(wall_async, 3),
        "sync_inv_per_s": round(tot_sync / max(wall_sync, 1e-9), 1),
        "async_inv_per_s": round(tot_async / max(wall_async, 1e-9), 1),
        "trace": compact_trace(stitched),
    }


async def run_live(mesh, out: dict) -> None:
    from stl_fusion_tpu.client import compute_client, install_compute_call_type
    from stl_fusion_tpu.cluster import ShardMap
    from stl_fusion_tpu.core import (
        ComputeService,
        FusionHub,
        TableBacking,
        compute_method,
        memo_table_of,
        set_default_hub,
    )
    from stl_fusion_tpu.diagnostics.metrics import global_metrics
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.graph.nonblocking import WavePipeline
    from stl_fusion_tpu.graph.synthetic import power_law_dag
    from stl_fusion_tpu.rpc import RpcHub
    from stl_fusion_tpu.rpc.fanout import install_compute_fanout
    from stl_fusion_tpu.rpc.testing import RpcTestTransport

    ns = int(os.environ.get("MESH_LIVE_NODES", 20_000))
    # 2 members by default: the kill phase must leave a member count that
    # still divides the device count evenly, or the reshard is a REBUILD
    # (legal, counted, but then nothing "moves" for the gate to verify)
    n_members = int(os.environ.get("MESH_LIVE_MEMBERS", 2))
    members = [f"m{i}" for i in range(n_members)]
    s2, d2 = power_law_dag(ns, avg_degree=3.0, seed=23)

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub, node_capacity=ns + 16, edge_capacity=len(s2) + 4096)

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
        backend.declare_row_edges(blk, s2, blk, d2)
        table.read_batch(np.arange(ns))
        backend.flush()

        smap = ShardMap.initial(members, n_shards=64)
        exchange = os.environ.get("MESH_EXCHANGE", "a2a")
        n_hosts = int(os.environ.get("MESH_HOSTS", "1"))
        # MESH_ASYNC=1 rides the whole live composition (pipeline ->
        # superround -> routed mirror) on the async wave program
        async_depth = (
            int(os.environ.get("MESH_ASYNC_DEPTH", "4"))
            if os.environ.get("MESH_ASYNC", "0") == "1"
            else 0
        )
        backend.enable_mesh_routing(
            smap, mesh=mesh, exchange=exchange,
            devices_per_host=(mesh.devices.size // n_hosts) if n_hosts > 1 else None,
            exchange_async=async_depth > 0, async_depth=async_depth,
        )

        adj = {}
        for u, v in zip(s2.tolist(), d2.tolist()):
            adj.setdefault(u, []).append(v)

        def bfs(seeds):
            seen, stack = set(), list(seeds)
            while stack:
                u = stack.pop()
                if u in seen:
                    continue
                seen.add(u)
                stack.extend(adj.get(u, ()))
            return seen

        # an EXTERNAL client subscribed over RPC: its fences legitimately
        # ride the relay; the gate is that no ON-MESH member's do
        server_rpc = RpcHub("server")
        client_rpc = RpcHub("client")
        install_compute_call_type(server_rpc)
        install_compute_call_type(client_rpc)
        server_rpc.add_service("rows", svc)
        fanout = install_compute_fanout(server_rpc, backend)
        fanout.set_mesh_scope(members, cluster_members=members)
        RpcTestTransport(client_rpc, server_rpc)
        client = compute_client("rows", client_rpc, FusionHub())
        sub_row = int(d2[0])
        await client.row(sub_row)

        # --- fused routed chains through the pipeline (the ISSUE 9 composition)
        pipe = WavePipeline(backend, fuse_depth=4)
        rng = np.random.default_rng(5)
        import asyncio

        rounds = 3
        groups_per_round = 4
        seen = set()
        divergence = 0
        t0 = time.time()
        for r in range(rounds):
            groups = [
                rng.choice(ns, size=3, replace=False).tolist()
                for _ in range(groups_per_round)
            ]
            if r == 0:
                # hit the external client's key: its fence must ride the
                # ordinary relay (it is NOT an on-mesh member) while the
                # mesh members' frontier stays on-device
                groups[0].append(sub_row)
            tickets = [pipe.submit_rows(blk, g) for g in groups]
            pipe.drain()
            await asyncio.sleep(0)  # let fence posts flush
            for g, t in zip(groups, tickets):
                want = {x for x in bfs(g) if x not in seen}
                seen |= want
                if t.count != len(want):
                    divergence += 1
        burst_s = time.time() - t0

        # --- mid-burst reshard: kill m{last} -> device shards MOVE
        new_map = smap.with_members(members[:-1])
        moves = backend.apply_mesh_reshard(new_map)
        shard_devices = check_shard_devices(
            backend.routed_mirror()["graph"], mesh, out, "live (after reshard)"
        )
        post_groups = [rng.choice(ns, size=3, replace=False).tolist() for _ in range(3)]
        tickets = [pipe.submit_rows(blk, g) for g in post_groups]
        pipe.drain()
        for g_, t in zip(post_groups, tickets):
            want = {x for x in bfs(g_) if x not in seen}
            seen |= want
            if t.count != len(want):
                divergence += 1
        # stats AFTER the post-reshard bursts: an eager fallback triggered
        # BY the reshard must fail the gate too (review finding — a
        # pre-reshard snapshot would mask exactly the disengagement the
        # gate exists to catch)
        stats = pipe.stats()
        pipe.dispose()

        snap = global_metrics().snapshot()
        routed_waves = int(snap.get("fusion_mesh_routed_waves_total", 0))
        levels_total = int(snap.get("fusion_mesh_exchange_levels_total", 0))
        if divergence:
            out["violations"].append(f"live oracle divergence in {divergence} wave(s)")
        if stats["eager_waves"] or not stats["fused_dispatches"]:
            out["violations"].append(
                f"pipeline disengaged from the fused routed path: {stats}"
            )
        if routed_waves == 0:
            out["violations"].append("fusion_mesh_routed_waves_total == 0")
        if fanout.mesh_member_relays:
            out["violations"].append(
                f"{fanout.mesh_member_relays} frontier fence(s) re-entered via "
                f"host RPC for on-mesh members"
            )
        if moves == 0:
            out["violations"].append("reshard moved zero device shards")
        rg = backend._routed_mirror["graph"]
        if async_depth > 0 and rg.quiescence_checks == 0:
            out["violations"].append(
                "live async ran zero quiescence checks (uncounted fallback "
                "to sync)"
            )
        # stitch the most recent wave the superround threaded through the
        # routed mirror — its cause id IS the wave's existing cause, so
        # /trace?cause=<id> and explain() name the same timeline
        from stl_fusion_tpu.diagnostics.mesh_telemetry import global_mesh_trace

        live_cause = rg.last_trace_cause or global_mesh_trace().latest_cause()
        live_trace = (
            global_mesh_trace().stitch(live_cause) if live_cause else None
        )
        if live_trace is None:
            out["violations"].append(
                "live leg recorded no wave trace segments (stitch hooks dark)"
            )
        out["live"] = {
            "nodes": ns,
            "members": n_members,
            "rounds": rounds,
            "burst_s": round(burst_s, 2),
            "pipeline": stats,
            "routed_waves": routed_waves,
            "exchange_levels": levels_total,
            "reshard_moves": int(moves),
            "reshard_epoch": new_map.epoch,
            "shard_devices": shard_devices,
            "oracle_divergence": divergence,
            "external_client_fences": fanout.drained_total,
            "mesh_member_relays": fanout.mesh_member_relays,
            "dcn_fallback_relays": fanout.dcn_fallback_relays,
            "async_depth": async_depth,
            "quiescence_checks": rg.quiescence_checks,
            "trace": compact_trace(live_trace),
        }
        await server_rpc.stop()
        await client_rpc.stop()
    finally:
        set_default_hub(old)


def main() -> None:
    # the mesh leg needs its own virtual device pool; the caller (CI)
    # sets XLA_FLAGS before python starts — assert, don't silently
    # measure a 1-device "mesh"
    import asyncio

    from stl_fusion_tpu.graph import enable_program_cache, require_accelerator
    from stl_fusion_tpu.parallel import graph_mesh

    device = require_accelerator("perf/mesh_path.py")
    enable_program_cache()
    n_dev = device["device_count"]
    if n_dev < 2:
        print(json.dumps(
            {**device, "error": f"mesh path needs >1 device, have {n_dev}"}
        ))
        sys.exit(2)
    mesh = graph_mesh()
    out: dict = {**device, "mesh_devices": n_dev, "violations": []}
    if os.environ.get("MESH_SKIP_STATIC", "0") != "1":
        run_static(mesh, out)
    if os.environ.get("MESH_ASYNC", "0") == "1":
        run_async_ab(mesh, out)
    if os.environ.get("MESH_SKIP_LIVE", "0") != "1":
        asyncio.run(run_live(mesh, out))
    if int(os.environ.get("MESH_MULTIHOST", "0")) >= 2:
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from mesh_multihost import run_multihost

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
