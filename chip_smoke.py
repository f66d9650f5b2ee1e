#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no children, the public API, at the size the repo calls real:
BASELINE.json config 5, the 10 M-node power-law DAG (average degree 3,
generated from ``--seed``) with 16-word rows (512 lanes per burst).

Legs (each ends in an oracle comparison; any divergence fails the run):

- **serve** (device 0): a ``TableBacking`` compute service on a ``FusionHub``
  with a ``TpuGraphBackend``; graph built by ``bind_table_rows`` →
  ``declare_row_edges`` → ``warm_block_on_device``; ``build_topo_mirror``;
  ``enable_nonblocking`` + ``enable_super_rounds``. Then
  (a) eight lone invalidations through ``cascade_rows_batch``, each timed by
  the host clock around the blocking call and compared with a host BFS
  closure (count and stale mask);
  (b) super-rounds of depth 3 of 512-group lane bursts with churn between
  them (device refresh of stale rows, ~2,000 declared edges a round, scalar
  recaptures), the bursts' lane counts compared with a host CSR BFS over the
  churned topology (the smoke's own record of every edge it declared);
  (c) writes end to end: a ``@command_handler`` executed by ``hub.commander``
  under the operations pipeline with an in-memory op-log, whose completion
  invalidates, rides a fused wave, and reaches an RPC client over
  ``RpcTestTransport(wire_codec=True)`` holding ``$sys-c`` subscriptions on
  the written and dependent keys; the client's re-read equals the store.
- **kernel**: the Pallas ``or_popcount`` compiled by Mosaic
  (``interpret=False``) on a bit vector of the serve leg's size, against
  numpy.
- **mesh** (only when JAX shows >= 4 TPU devices; never a virtual pool of its
  own making): perf/mesh_path.py's routed legs in this process (the one
  import from ``perf/``): static routed graph at 10 M nodes per chip,
  ``exchange="a2a"``, wave 0 equal to the host BFS mask; live hub +
  ``enable_mesh_routing`` + fused chains + a mid-burst reshard at >= 1 M
  nodes; every array's shards verified on the mesh's distinct devices.

Every counted fallback on that path (watchdog faults/fallbacks, super-round
eager rounds/faults/restages/forced harvests, pipeline eager waves/chain
faults, tree/hier exchange fallbacks, mesh member relays) is printed and
must be zero. Progress goes to stderr. Stdout carries two lines, each one
JSON object: first the record (sizes, ``reduced``, per-leg results, fallback
counters, seconds, compile cache, HBM peak; its times are smoke observations,
not benchmark results), then, LAST, the verdict the driver reads, which has
exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.

Exit code: 0 only when JAX's first device is a TPU, every selected leg
passed, every oracle agreed and every fallback counter is zero. Without a
TPU the script exits 2 before building anything and prints no result. The
one exception is ``--cpu-dry-run``: a tiny CPU run (tests, debugging before
spending chip time) that says ``"platform": "cpu"`` and runs Pallas
interpreted because the flag said so, not because no chip was found.
"""
import argparse
import asyncio
import dataclasses
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
FULL_NODES = 10_000_000  # BASELINE.json config 5
MIN_NODES = 1_000_000  # the floor a cut may reach on the chip
LANE_GROUPS = 512  # 16-word rows x 32 lanes
MAX_WORDS = 16
SEEDS_PER_GROUP = 8
FUSE_DEPTH = 3
SUPER_ROUNDS = 2  # checked super-rounds, after the warm ones
# the resident program has two variants (the memo validity mask folded
# in-program or deferred to the host, decided per dispatch from the table's
# state), so a second super-round can compile again: the warm runs two, and
# the checked ones compile nothing
WARM_SUPER_ROUNDS = 2
EDGE_CHURN = 2000  # declared edges per round
SCALAR_CHURN = 4  # scalar recaptures per round
LONE_WAVES = 8
LEGS = ("serve", "kernel", "mesh")


def note(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0, help="graph and workload seed")
    p.add_argument(
        "--nodes", type=int, default=FULL_NODES,
        help=f"serve-leg graph size (a cut below {FULL_NODES:,} is printed "
        f"under 'reduced'; never below {MIN_NODES:,} on the chip)",
    )
    p.add_argument(
        "--legs", default=",".join(LEGS),
        help="comma-separated subset of serve,kernel,mesh (default: all)",
    )
    p.add_argument(
        "--cpu-dry-run", action="store_true",
        help="explicit tiny CPU run: forces JAX_PLATFORMS=cpu, Pallas interpreted",
    )
    p.add_argument(
        "--inject-fault", action="store_true",
        help="arm the watchdog's chaos hook before the first lone wave: the "
        "host loop still answers right, and the smoke must exit nonzero",
    )
    args = p.parse_args(argv)
    args.legs = tuple(x for x in args.legs.split(",") if x)
    unknown = [x for x in args.legs if x not in LEGS]
    if unknown:
        p.error(f"unknown leg(s) {unknown}; choose from {LEGS}")
    if not args.cpu_dry_run and args.nodes < MIN_NODES:
        p.error(f"--nodes below {MIN_NODES:,} is only allowed with --cpu-dry-run")
    return args


# --------------------------------------------------------------------- oracles
class HostGraph:
    """The smoke's own record of the dependency topology (the generated DAG
    plus every churn edge it declared), with a vectorized CSR BFS: the
    plain reference the device answers are held to. Shares no code and no
    state with the system under test."""

    def __init__(self, src: np.ndarray, dst: np.ndarray, n: int):
        self.n = n
        self._src = [np.asarray(src, dtype=np.int64)]
        self._dst = [np.asarray(dst, dtype=np.int64)]
        self._csr = None

    def add_edges(self, src: np.ndarray, dst: np.ndarray) -> None:
        self._src.append(np.asarray(src, dtype=np.int64))
        self._dst.append(np.asarray(dst, dtype=np.int64))
        self._csr = None

    def _tables(self):
        if self._csr is None:
            src = np.concatenate(self._src)
            dst = np.concatenate(self._dst)
            order = np.argsort(src, kind="stable")
            starts = np.zeros(self.n + 1, dtype=np.int64)
            np.add.at(starts[1:], src, 1)
            self._csr = (np.cumsum(starts), dst[order])
        return self._csr

    def out_neighbors(self, node: int) -> np.ndarray:
        starts, nbr = self._tables()
        return nbr[starts[node]:starts[node + 1]]

    def closure(self, seeds) -> np.ndarray:
        """bool[n]: the seeds and everything that transitively depends on
        them (the invalidation a wave from a clean state must produce)."""
        starts, nbr = self._tables()
        seen = np.zeros(self.n, dtype=bool)
        frontier = np.unique(np.asarray(seeds, dtype=np.int64))
        seen[frontier] = True
        while frontier.size:
            s0 = starts[frontier]
            cnt = starts[frontier + 1] - s0
            total = int(cnt.sum())
            if total == 0:
                break
            idx = np.repeat(s0 - np.cumsum(cnt) + cnt, cnt) + np.arange(total)
            cand = nbr[idx]
            cand = np.unique(cand[~seen[cand]])
            seen[cand] = True
            frontier = cand
        return seen


# --------------------------------------------------------------------- service
def make_dag_service(n: int):
    """The table-backed DAG service the benchmark's cells measure
    (``benchmarks/deployments/table_dag.py``: row i's value derives from a
    base array, the store; device loader with the base table in HBM), plus
    the one write this smoke issues."""
    from deployments.table_dag import make_service as make_dag_table

    from stl_fusion_tpu.commands import command_handler
    from stl_fusion_tpu.core import is_invalidating
    from stl_fusion_tpu.utils.serialization import wire_type

    @wire_type("ChipSmokeBump")
    @dataclasses.dataclass(frozen=True)
    class Bump:
        """One write: a NON-idempotent increment of a row's stored value."""

        row: int
        delta: float

        def shard_key(self):
            return f"row-{self.row}"

    class DagTable(make_dag_table(n)):
        @command_handler
        async def bump(self, command: Bump):
            if is_invalidating():
                await self.node(command.row)
                return
            self.base[command.row] += command.delta
            self._base_dev = None
            return float(self.base[command.row])

    return DagTable, Bump


class Observer:
    """Counts client-observed invalidations with SYNC callbacks (the
    callback runs inside the node's invalidation: the moment a client
    reader would see staleness)."""

    def __init__(self):
        self.remaining = 0
        self.event = asyncio.Event()

    def arm(self, count: int) -> None:
        self.remaining = count
        self.event.clear()

    def hit(self, _c=None) -> None:
        self.remaining -= 1
        if self.remaining <= 0:
            self.event.set()


async def settle(seconds: float = 0.05) -> None:
    """Let queued tasks (watch registrations, outbox drains) run."""
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline:
        await asyncio.sleep(0.005)


# ------------------------------------------------------------------- serve leg
async def serve_leg(args, out: dict) -> None:
    import jax

    from stl_fusion_tpu.client import compute_client, install_compute_call_type
    from stl_fusion_tpu.commands import ClusterCommander
    from stl_fusion_tpu.core import (
        FusionHub,
        capture,
        invalidating,
        memo_table_of,
        set_default_hub,
    )
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.graph.program_cache import (
        program_warm_report,
        time_program_warm,
    )
    from stl_fusion_tpu.graph.synthetic import power_law_dag
    from stl_fusion_tpu.native import load_graphpack
    from stl_fusion_tpu.oplog import (
        InMemoryOperationLog,
        LocalChangeNotifier,
        attach_operation_log,
    )
    from stl_fusion_tpu.resilience import WaveWatchdog
    from stl_fusion_tpu.rpc import RpcHub, RpcTestTransport, install_compute_fanout

    n = args.nodes
    # a dry run's toy graph takes churn in proportion (2,000 edges a round
    # on 20 k nodes is a rebuild a round, not churn); real sizes take it whole
    edge_churn = EDGE_CHURN if n >= MIN_NODES else max(n // 250, 8)
    if edge_churn != EDGE_CHURN:
        out["reduced"].append(
            {"what": "edge_churn_per_round", "from": EDGE_CHURN, "to": edge_churn}
        )
    rng = np.random.default_rng(args.seed)
    problems: list = out["problems"]
    seconds: dict = out["seconds"]

    note(f"serve: generating the {n:,}-node power-law DAG (seed {args.seed})...")
    t0 = time.perf_counter()
    src, dst = power_law_dag(n, avg_degree=3.0, seed=args.seed)
    seconds["graph_generate"] = round(time.perf_counter() - t0, 2)
    oracle = HostGraph(src, dst, n)
    out["sizes"].update(nodes=n, edges=int(len(src)), lane_groups=LANE_GROUPS,
                        row_words=MAX_WORDS)

    hub = FusionHub()
    old_hub = set_default_hub(hub)
    try:
        total_rounds = FUSE_DEPTH * (SUPER_ROUNDS + WARM_SUPER_ROUNDS)
        backend = TpuGraphBackend(
            hub,
            node_capacity=n + 64,
            # headroom for the declared churn: an edge-capacity grow would
            # dirty the device mirror and force a dense re-upload mid-leg
            edge_capacity=len(src) + max(65536, 4 * edge_churn * total_rounds),
        )
        # every fused dispatch from here on goes through the watchdog, so
        # a device fault that the host loop would quietly absorb is COUNTED.
        # The deadline covers a cold compile: only faults degrade.
        watchdog = backend.attach_watchdog(WaveWatchdog(deadline_s=1200.0))
        Dag, Bump = make_dag_service(n)
        svc = Dag(hub)
        hub.add_service(svc, "dag")
        hub.commander.add_service(svc)
        log_store = InMemoryOperationLog()
        reader = attach_operation_log(hub.commander, log_store, LocalChangeNotifier())
        table = memo_table_of(svc.node)

        note("serve: columnar build (bind_table_rows, declare_row_edges, device warm)...")
        t0 = time.perf_counter()
        block = backend.bind_table_rows(table)
        backend.declare_row_edges(block, src, block, dst)
        backend.warm_block_on_device(block)
        backend.flush()
        seconds["graph_build"] = round(time.perf_counter() - t0, 2)
        if backend.node_count != n or table.stale_count() != 0:
            problems.append("serve: the built graph is not the declared one")
        gdev = backend.graph
        out["default_device"] = str(jax.devices()[0])

        note("serve: building the topo mirror...")
        t0 = time.perf_counter()
        mirror = gdev.build_topo_mirror()
        seconds["mirror_build"] = round(time.perf_counter() - t0, 2)
        out["mirror"] = {
            "levels": mirror["levels"],
            "disk_cache": "hit" if gdev.mirror_cache_hits else "miss",
        }
        out["native_graphpack"] = (
            "loaded" if load_graphpack() is not None else "numpy path served"
        )
        pipe = hub.enable_nonblocking(fuse_depth=8, max_words=MAX_WORDS)
        sr = backend.enable_super_rounds(block, depth=FUSE_DEPTH, max_words=MAX_WORDS)

        def restore() -> None:
            """Recompute what a wave left stale and WAIT for the device:
            the refresh is an asynchronous O(n) program, and a timed sample
            that follows must not be charged for it."""
            if table.stale_count():
                backend.refresh_block_on_device(block)
            backend.flush()
            jax.device_get(table.values[:1])

        # ---- (a) lone invalidations, each against the host BFS closure
        with time_program_warm("union", key=(n, "lat+topo")):
            # a shallow wave compiles the lat kernel, a deep one overflows
            # it into the fused topo union: both serve the samples below
            backend.cascade_rows_batch(block, [n - 1])
            backend.cascade_rows_batch(block, [n // 20])
        with time_program_warm("refresh", key=(n,)):
            restore()
        if args.inject_fault:
            watchdog.inject_fault_next()
        note(f"serve: {LONE_WAVES} lone invalidations against the host BFS...")
        # half tail rows (shallow closures, the shape of a typical edit),
        # a quarter mid-range, a quarter low ids (deep closures that
        # overflow the lat mirror into the fused topo union)
        half, quarter = LONE_WAVES // 2, LONE_WAVES // 4
        rows = (n - 1 - rng.choice(n // 100, size=half, replace=False)).tolist()
        rows += (n // 2 + rng.choice(n // 4, size=quarter, replace=False)).tolist()
        rows += (n // 20 + rng.choice(n // 5, size=quarter, replace=False)).tolist()
        lone_ms, lone_sizes, lone_bad = [], [], 0
        lat0 = gdev.lat_waves
        for row in rows:
            t0 = time.perf_counter()
            count = backend.cascade_rows_batch(block, [row])
            lone_ms.append((time.perf_counter() - t0) * 1e3)
            want = oracle.closure([row])
            stale = ~np.asarray(table.valid_mask)
            if count != int(want.sum()) or not np.array_equal(stale, want):
                lone_bad += 1
            lone_sizes.append(int(count))
            restore()
        if lone_bad:
            problems.append(f"serve: {lone_bad} lone wave(s) diverged from the host BFS")
        out["lone_waves"] = {
            "samples": LONE_WAVES,
            "ms": [round(x, 3) for x in lone_ms],
            "ms_median": float(np.median(lone_ms)),
            "closure_sizes": lone_sizes,
            "diverged": lone_bad,
            "served_by_lat_mirror": gdev.lat_waves - lat0,
        }

        # ---- (b) super-rounds with churn between them
        group_ids = [
            rng.choice(n // 10, size=SEEDS_PER_GROUP, replace=False).tolist()
            for _ in range(LANE_GROUPS)
        ]
        indeg = np.bincount(dst, minlength=n)
        low_indeg = np.nonzero(indeg[: n // 2] <= 4)[0]
        scalar_rows = rng.choice(
            low_indeg, size=SCALAR_CHURN * total_rounds, replace=False
        )
        churn = {"edges": 0, "scalar": 0}

        async def prep_churn(round_base: int) -> None:
            """One super-round's churn: declared edges that FOLLOW the
            mirror's level order (a dependency on something computed
            earlier: acyclic, and it patches the mirror in place), plus
            scalar recaptures of low-in-degree rows. Journal-only host
            work; the flush before the next dispatch applies it."""
            for _ in range(FUSE_DEPTH):
                a = rng.integers(0, n, size=edge_churn)
                b = rng.integers(0, n, size=edge_churn)
                la = gdev.mirror_levels(block.base + a)
                lb = gdev.mirror_levels(block.base + b)
                keep = la != lb
                u = np.where(la < lb, a, b)[keep]
                v = np.where(la < lb, b, a)[keep]
                churn["edges"] += backend.declare_row_edges(block, u, block, v)
                oracle.add_edges(u, v)
            for j in range(FUSE_DEPTH * SCALAR_CHURN):
                row = int(scalar_rows[round_base * SCALAR_CHURN + j])
                with invalidating():
                    await svc.node(row)
                await svc.node(row)
                churn["scalar"] += 1

        note(f"serve: super-rounds (depth {FUSE_DEPTH}, {LANE_GROUPS} groups x "
             f"{SEEDS_PER_GROUP} seeds) with churn between them...")
        sr_inv = 0
        last_burst = None

        async def super_round(i: int):
            await prep_churn(i * FUSE_DEPTH)
            staged = sr.stage([group_ids] * FUSE_DEPTH)
            backend.flush()
            backend.refresh_block_on_device(block)
            t0 = time.perf_counter()
            per_burst = sr.dispatch(staged).harvest()
            dt = time.perf_counter() - t0
            inv = sum(int(c.sum()) for c in per_burst)
            warm = " (warm)" if i < WARM_SUPER_ROUNDS else ""
            note(f"serve: super-round {i}{warm}: {inv:,} invalidations in {dt:.2f}s")
            return per_burst, inv, dt

        with time_program_warm("superround", key=(n, LANE_GROUPS, FUSE_DEPTH)):
            for i in range(WARM_SUPER_ROUNDS):
                await super_round(i)
        for i in range(WARM_SUPER_ROUNDS, WARM_SUPER_ROUNDS + SUPER_ROUNDS):
            last_burst, inv, dt = await super_round(i)
            sr_inv += inv
            seconds.setdefault("superrounds", []).append(round(dt, 3))
        note("serve: lane counts against the host CSR BFS on the churned topology...")
        probe = sorted(set(range(0, LANE_GROUPS, max(LANE_GROUPS // 3, 1))))[:3]
        sr_bad = 0
        for rnd in (0, FUSE_DEPTH - 1):
            for gi in probe:
                want = int(oracle.closure(block.base + np.asarray(group_ids[gi])).sum())
                if int(last_burst[rnd][gi]) != want:
                    sr_bad += 1
                    note(f"serve: round {rnd} group {gi}: device "
                         f"{int(last_burst[rnd][gi])} != oracle {want}")
        if sr_bad:
            problems.append(f"serve: {sr_bad} lane count(s) diverged from the host BFS")
        restore()
        out["superrounds"] = {
            "checked": SUPER_ROUNDS, "depth": FUSE_DEPTH,
            "invalidations": sr_inv,
            "churn_edges_declared": churn["edges"],
            "churn_scalar_recaptures": churn["scalar"],
            "lane_counts_checked": 2 * len(probe),
            "diverged": sr_bad,
            "mirror_patches": gdev.mirror_patches,
            "mirror_rebuilds": gdev.mirror_rebuilds,
        }

        # ---- (c) writes end to end, observed by an RPC client
        note("serve: writes through the commander to an RPC client...")
        with time_program_warm("wave_chain", key=(n, MAX_WORDS, 2)):
            # the pipeline's path for the two command waves below: small
            # waves ride the lat program the lone leg compiled, so this
            # warms nothing new unless the lat mirror declines them
            pipe.submit_rows(block, [n - 1])
            pipe.submit_rows(block, [n - 2])
            pipe.drain()
        restore()
        server_rpc = RpcHub("server")
        install_compute_call_type(server_rpc)
        server_rpc.add_service("dag", svc)
        install_compute_fanout(server_rpc, backend)
        client_rpc = RpcHub("client-0")
        install_compute_call_type(client_rpc)
        RpcTestTransport(client_rpc, server_rpc, wire_codec=True)
        proxy = compute_client("dag", client_rpc, FusionHub(), peer_ref="c0")
        # two written rows with dependents, plus up to three dependents each
        outdeg = np.bincount(src, minlength=n)
        writers = rng.choice(np.nonzero(outdeg[n // 2:] >= 2)[0] + n // 2,
                             size=2, replace=False).tolist()
        keys = []
        for w in writers:
            keys.append(int(w))
            keys.extend(int(d) for d in oracle.out_neighbors(w)[:3])
        keys = sorted(set(keys))
        observer = Observer()
        observer.arm(len(keys))
        before = {}
        for k in keys:
            computed = await capture(lambda k=k: proxy.node(k))
            before[k] = computed.value
            computed.on_invalidated(observer.hit)
        await settle()
        backend.flush()  # absorb the subscriptions' scalar-twin journal
        commander = ClusterCommander(
            hub.commander, member_id="m0", log_store=log_store
        )
        routed0 = pipe.stats()
        t0 = time.perf_counter()
        deltas = {writers[0]: 7.0, writers[1]: 11.0}
        for i, (w, delta) in enumerate(deltas.items()):
            await commander.call(Bump(int(w), delta), operation_id=f"smoke-op-{i}")
        commander.drain()
        write_bad = []
        try:
            await asyncio.wait_for(observer.event.wait(), 60.0)
        except asyncio.TimeoutError:
            write_bad.append(f"{observer.remaining} subscription(s) never invalidated")
        visible_ms = (time.perf_counter() - t0) * 1e3
        for k in keys:
            got = await proxy.node(k)
            want = before[k] + deltas.get(k, 0.0)
            if got != float(svc.base[k]) or got != want:
                write_bad.append(f"key {k}: client {got}, store {svc.base[k]}, want {want}")
        journaled = [log_store.contains(f"smoke-op-{i}") for i in range(len(deltas))]
        if not all(journaled):
            write_bad.append(f"op-log is missing a write: {journaled}")
        routed = pipe.stats()
        lat_served = (routed["lat_waves"] + routed["lat_overflow_waves"]
                      - routed0["lat_waves"] - routed0["lat_overflow_waves"])
        if routed["eager_waves"] != routed0["eager_waves"] or not (
            lat_served == len(deltas)
            or routed["fused_dispatches"] > routed0["fused_dispatches"]
        ):
            write_bad.append(
                "the command waves rode neither the lat mirror nor a fused "
                f"dispatch (before {routed0}, after {routed})"
            )
        if write_bad:
            problems.append("serve: write leg: " + "; ".join(write_bad))
        out["write"] = {
            "commands": len(deltas), "subscribed_keys": len(keys),
            "journaled": all(journaled),
            "waves_lat_served": lat_served,
            "command_to_all_visible_ms": round(visible_ms, 2),
            "client_equals_store": not write_bad,
        }
        await client_rpc.stop()
        await server_rpc.stop()
        await reader.stop()

        sr_stats, pipe_stats = sr.stats(), pipe.stats()
        out["fallbacks"].update({
            "watchdog_faults": watchdog.faults,
            "watchdog_fallbacks": watchdog.fallbacks,
            "watchdog_deadline_trips": watchdog.deadline_trips,
            "superround_eager_rounds": sr_stats["eager_rounds"],
            "superround_faults": sr_stats["faults"],
            "superround_restages": sr_stats["restages"],
            "superround_forced_harvests": sr_stats["journal_forced_harvests"],
            "pipeline_eager_waves": pipe_stats["eager_waves"],
            "pipeline_chain_faults": pipe.chain_faults,
        })
        out["lone_waves"]["not_served_by_lat_mirror"] = (
            LONE_WAVES - out["lone_waves"]["served_by_lat_mirror"]
        )
        if not sr_stats["superrounds_dispatched"]:
            problems.append("serve: zero resident super-round dispatches")
        out["program_warms"] = program_warm_report()
        pipe.dispose()
        sr.dispose()
    finally:
        set_default_hub(old_hub)


# ------------------------------------------------------------------ kernel leg
def kernel_leg(args, out: dict) -> None:
    import jax.numpy as jnp

    from stl_fusion_tpu.ops.pallas_kernels import or_popcount

    interpret = bool(args.cpu_dry_run)
    words = (args.nodes + 31) // 32
    note(f"kernel: or_popcount over {words:,} words, interpret={interpret}...")
    rng = np.random.default_rng(args.seed)
    new = rng.integers(-(2**31), 2**31, size=words, dtype=np.int64).astype(np.int32)
    old = rng.integers(-(2**31), 2**31, size=words, dtype=np.int64).astype(np.int32)
    t0 = time.perf_counter()
    merged, count = or_popcount(jnp.asarray(new), jnp.asarray(old), interpret=interpret)
    merged, count = np.asarray(merged), int(count)
    out["seconds"]["or_popcount_compile_and_run"] = round(time.perf_counter() - t0, 3)
    want = int(np.bitwise_count((new & ~old).view(np.uint32)).sum())
    ok = bool(np.array_equal(merged, new | old)) and count == want
    out["kernels"] = {
        "or_popcount": {"interpret": interpret, "words": words, "matches_numpy": ok},
        "ring_all_gather": "deleted in PR 21 (never compiled by Mosaic; ROADMAP D3)",
    }
    if not ok:
        out["problems"].append("kernel: or_popcount diverged from numpy")


# -------------------------------------------------------------------- mesh leg
def mesh_leg(args, out: dict) -> None:
    """perf/mesh_path.py's routed legs, in this process over every device
    JAX shows. Sizes ride the environment because that is how those legs
    take them (ROADMAP D6)."""
    import mesh_path

    from stl_fusion_tpu.parallel import graph_mesh

    mesh = graph_mesh()
    n_dev = int(mesh.devices.size)
    per_chip = args.nodes
    live_nodes = 12_000 if args.cpu_dry_run else MIN_NODES
    os.environ.update(
        MESH_NODES=str(per_chip * n_dev), MESH_EXCHANGE="a2a", MESH_MEMBERS="4",
        MESH_WAVES="2",
        MESH_SEEDS=str(max(per_chip * n_dev // 800, 8)),
        MESH_LIVE_NODES=str(live_nodes),
    )
    rec: dict = {"violations": []}
    t0 = time.perf_counter()
    mesh_path.run_static(mesh, rec)
    out["seconds"]["mesh_static"] = round(time.perf_counter() - t0, 1)
    t0 = time.perf_counter()
    asyncio.run(mesh_path.run_live(mesh, rec))
    out["seconds"]["mesh_live"] = round(time.perf_counter() - t0, 1)
    st, lv = rec.get("static", {}), rec.get("live", {})
    out["mesh"] = {
        "devices": n_dev,
        "static": {k: st.get(k) for k in (
            "nodes", "edges", "members", "exchange", "oracle_exact",
            "shard_devices", "total_invalidated", "wave_s", "build_s",
            "compile_s", "exchange_levels",
        )},
        "live": {k: lv.get(k) for k in (
            "nodes", "members", "routed_waves", "reshard_moves",
            "oracle_divergence", "shard_devices",
        )},
        "violations": rec["violations"],
    }
    out["sizes"]["mesh_nodes"] = st.get("nodes")
    out["sizes"]["mesh_live_nodes"] = lv.get("nodes")
    from stl_fusion_tpu.diagnostics.metrics import global_metrics

    snap = global_metrics().snapshot()
    out["fallbacks"].update({
        "mesh_member_relays": lv.get("mesh_member_relays"),
        "mesh_pipeline_eager_waves": (lv.get("pipeline") or {}).get("eager_waves"),
        "tree_fallbacks": int(snap.get("fusion_mesh_tree_fallback_total", 0)),
        "hier_fallbacks": int(snap.get("fusion_mesh_hier_fallback_total", 0)),
    })
    if len(set(st.get("shard_devices") or ())) != n_dev:
        rec["violations"].append("static shards are not on distinct devices")
    out["problems"].extend(f"mesh: {v}" for v in rec["violations"])


# ------------------------------------------------------------------------ main
def _versions() -> dict:
    from importlib import metadata

    found = {}
    for name in ("jax", "jaxlib", "libtpu"):
        try:
            found[name] = metadata.version(name)
        except metadata.PackageNotFoundError:
            found[name] = None
    return found


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.cpu_dry_run:
        os.environ["JAX_PLATFORMS"] = "cpu"  # the flag IS the explicit request
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu" and not args.cpu_dry_run:
        note(f"chip_smoke: JAX found no TPU ({device}); nothing was built. "
             "A CPU dry run has to be asked for with --cpu-dry-run.")
        return 2
    # the package; benchmarks/ for the table service its cells measure;
    # perf/ for the mesh leg's routed legs (perf/mesh_path.py)
    sys.path[:0] = [HERE, os.path.join(HERE, "benchmarks"), os.path.join(HERE, "perf")]
    import jax.numpy as jnp

    from stl_fusion_tpu.graph import enable_program_cache, program_cache_stats

    cache = enable_program_cache()
    out: dict = {
        "ok": False,
        "device": device,
        "platform": device["platform"],
        "device_kind": device["kind"],
        "device_count": device["count"],
        "versions": _versions(),
        "seed": args.seed,
        "legs_selected": list(args.legs),
        "sizes": {},
        "reduced": (
            [] if args.nodes == FULL_NODES
            else [{"what": "nodes", "from": FULL_NODES, "to": args.nodes}]
        ),
        "legs": {},
        "fallbacks": {},
        "problems": [],
        "seconds": {},
        "compile_cache": {
            "dir": cache["jax_cache_dir"], "from_env": cache["from_env"],
            "entries_before": program_cache_stats()["entries"],
        },
        "mirror_cache_dir": cache["mirror_cache_dir"],
        "note": "times are smoke observations, not benchmark results",
    }
    note(f"chip_smoke on {device}, compile cache {cache['jax_cache_dir']} "
         f"({out['compile_cache']['entries_before']} entries)")

    # the round trip every blocking host<->device exchange pays
    x = jnp.zeros(8)
    bump = jax.jit(lambda v: v + 1)
    float(bump(x).sum())
    trips = []
    for _ in range(24):
        t0 = time.perf_counter()
        float(bump(x).sum())
        trips.append((time.perf_counter() - t0) * 1e3)
    out["dispatch_roundtrip_ms_median"] = float(np.median(trips))

    def run_leg(name: str, fn) -> None:
        if name not in args.legs:
            out["legs"][name] = "not run: not selected"
            return
        before = len(out["problems"])
        t0 = time.perf_counter()
        try:
            fn()
        except Exception as e:  # noqa: BLE001 — a leg that dies is a failed
            # leg; the others still run and the exit code says so
            import traceback

            traceback.print_exc()
            out["problems"].append(f"{name}: {type(e).__name__}: {e}")
        out["seconds"][f"{name}_leg"] = round(time.perf_counter() - t0, 1)
        out["legs"][name] = {"ok": len(out["problems"]) == before}

    run_leg("serve", lambda: asyncio.run(serve_leg(args, out)))
    run_leg("kernel", lambda: kernel_leg(args, out))
    # the mesh leg is for real chips; a dry run reaches it only when the
    # CALLER provided the device pool (this script never builds one)
    if device["count"] >= 4:
        run_leg("mesh", lambda: mesh_leg(args, out))
    else:
        out["legs"]["mesh"] = out["mesh"] = f"not run: {device['count']} device(s)"

    nonzero = {k: v for k, v in out["fallbacks"].items() if v}
    if nonzero:
        out["problems"].append(f"fallback counters nonzero: {nonzero}")
    stats = devices[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    out["compile_cache"]["entries_after"] = program_cache_stats()["entries"]
    out["ok"] = not out["problems"]
    print(json.dumps(out, separators=(",", ":")))
    # the verdict: the last stdout line, these two keys and no others
    print(json.dumps({"ok": out["ok"], "device": device}), flush=True)
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
