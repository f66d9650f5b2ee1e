#!/usr/bin/env python
"""Edge-tier benchmark: 1M simulated subscribers behind the live burst.

ISSUE 8 acceptance: the first measurement where "millions of users" is a
number, not a slogan. The stack under test, end to end:

- **server**: the live-path stack (FusionHub + TpuGraphBackend + a
  table-backed DAG service, columnar bulk ingest, topo mirror) driving
  lane-packed bursts over EDGE_GRAPH_NODES rows — every fence leaves the
  server as a coalesced ``$sys-c`` batch frame;
- **edges**: EDGE_NODES in-process EdgeNode gateways, each on its own
  RpcHub over a codec-faithful twisted channel pair, each holding EXACTLY
  ONE upstream subscription per distinct key (asserted, and
  metric-asserted in smoke mode);
- **sessions**: EDGE_SESSIONS simulated end-user sessions spread over the
  edges, each subscribed to EDGE_KEYS_PER_SESSION keys drawn zipf-style
  from EDGE_KEYS distinct keys (popularity skew: the hottest key carries
  a large share of the fan-out). Sessions are synchronous-sink
  EdgeSessions — client-visible the moment the sink returns — because a
  million pump tasks would measure the scheduler, not the fan-out.
- **measurement**: per round the burst fences every distinct key; the
  recorded numbers are when each session OBSERVED its frame. Reported:
  ``fenced_per_s`` (session deliveries / post-burst fan-out seconds),
  ``delivery_ms_p50/p99`` — fence (server wave apply) → client-visible —
  read from the system's own ``fusion_edge_delivery_ms`` histogram
  (checkpoint-diffed per round), and ``per_edge_rss_mb`` (resident-set
  delta of building the edges + sessions, divided by EDGE_NODES).

Hard asserts (the script FAILS on violation, so CI can run it as a gate):
upstream subscriptions per edge == distinct keys (single-upstream
coalescing engaged — not sessions×keys fan-in), zero evictions (no
session stalled), every expected delivery arrived.

EDGE_SMOKE=1 additionally boots a real EdgeHttpServer, attaches live SSE
consumers over TCP, and asserts the `/metrics` exposition shows
``fusion_edge_sessions``, a non-empty ``fusion_edge_delivery_ms``
histogram and the upstream-subscription invariant — the tier1.yml step.

ISSUE 10 additions — the serialize-once multi-process delivery plane:

- with **EDGE_WORKERS > 0** (the default) each edge runs an
  ``EdgeWorkerPool``: the parent EdgeNode keeps the upstream
  subscriptions and encodes each fenced frame ONCE; the simulated
  sessions live in N OS worker processes that receive the shared bytes
  over a pipe and pay the per-session envelope assembly — deliveries/s
  scales with processes instead of the one-interpreter fan loop.
  ``EDGE_WORKERS=0`` is the single-process A/B (the PR 8 shape).
- **amortization invariant (hard assert)**: encodes ≈ distinct fenced
  (key, version) pairs and ≪ deliveries — any per-session encode
  re-entry fails the run; the encode ratio (deliveries per encode) must
  clear a floor scaled to the configured fan-out (100 at the canonical
  zipf workload).
- **EDGE_FAN_WORKERS** sets the parent's fan-shard count (the in-parent
  session partitions drained concurrently).

ISSUE 11 additions — the upstream value plane (what the fence→visible
p99 now measures is the upstream re-read storm, so this is where it
amortizes):

- **EDGE_VALUE_PLANE** selects the upstream serving mode:
  ``block`` (default) = publish-on-wave value blocks: the server
  recomputes the burst's hot-set once, pushes ONE columnar
  ``value_block`` frame per edge, and a block-warm burst costs ZERO
  per-key upstream re-read RPCs (hard gate); ``batch`` = batched
  multi-key re-read only (one ``recompute_batch`` frame per edge per
  burst); ``perkey`` = the PR 10 per-key A/B shape.
- **value-plane gates (hard asserts)**: per-key upstream re-read RPCs
  ≤ keys on the first burst in batch/block modes, == 0 across the
  MEASURED bursts; in block mode the measured bursts must also add
  ZERO batch frames (the block was the fence AND the value) and every
  fence must be a block hit.
- reported: ``upstream_rpcs_per_burst``, ``block_hit_ratio``,
  ``reread_batch_size``.
- EDGE_SMOKE additionally drives a WebSocket consumer when the optional
  ``websockets`` package is installed (the WS load leg).
- **EDGE_ACCEPT_PLANE** (``send_fds`` default / ``reuseport``) selects
  the worker pool's socket-ownership plane (portable resume tokens vs
  kernel-hash placement).

Env: EDGE_GRAPH_NODES (default 2_000_000), EDGE_NODES (4), EDGE_SESSIONS
(1_000_000), EDGE_KEYS (512), EDGE_KEYS_PER_SESSION (2), EDGE_ZIPF (1.1),
EDGE_ROUNDS (2), EDGE_GROUPS (16), EDGE_SEEDS_PER_GROUP (2),
EDGE_TIMEOUT_S (600), EDGE_WIRE (1), EDGE_SMOKE (0), EDGE_WORKERS (2),
EDGE_FAN_WORKERS (2), EDGE_VALUE_PLANE (block), EDGE_ACCEPT_PLANE
(send_fds).

Prints ONE JSON line (stdout); progress notes go to stderr.
"""
import asyncio
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def note(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


from stl_fusion_tpu.client import install_compute_call_type  # noqa: E402
from stl_fusion_tpu.core import (  # noqa: E402
    ComputeService,
    FusionHub,
    TableBacking,
    compute_method,
    memo_table_of,
    set_default_hub,
)
from stl_fusion_tpu.diagnostics import global_metrics  # noqa: E402
from stl_fusion_tpu.edge import EdgeNode, EdgeWorkerPool  # noqa: E402
from stl_fusion_tpu.graph import TpuGraphBackend  # noqa: E402
from stl_fusion_tpu.graph.synthetic import power_law_dag  # noqa: E402
from stl_fusion_tpu.rpc import RpcHub, RpcTestTransport  # noqa: E402


def make_dag_service(n: int):
    class DagTable(ComputeService):
        """The benchmark DAG as a table-backed service: row values derive
        from a base array; device loader serves warms/refreshes."""

        def __init__(self, hub=None):
            super().__init__(hub)
            self.base = np.arange(n, dtype=np.float32)
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
                rows=n, batch="load",
                device_batch="load_dev", device_args="load_dev_args",
            )
        )
        async def node(self, i: int) -> float:
            return float(self.base[i])

    return DagTable


class Observer:
    """Counts fence deliveries across ALL sessions (one shared sink per
    edge — a million per-session closures would be pure overhead)."""

    def __init__(self):
        self.fenced = 0
        self.expected = 0
        self.event = asyncio.Event()

    def arm(self, expected: int) -> None:
        self.fenced = 0
        self.expected = expected
        self.event.clear()

    def sink(self, frame) -> None:
        # fence frames carry the wave-apply origin timestamp; initial
        # attach frames do not and stay uncounted
        if frame[4] is not None:
            self.fenced += 1
            if self.fenced >= self.expected:
                self.event.set()


def rss_mb() -> float:
    with open("/proc/self/status") as f:
        for line in f:
            if line.startswith("VmRSS:"):
                return int(line.split()[1]) / 1024.0
    return 0.0


def zipf_weights(n: int, a: float) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = 1.0 / ranks**a
    return w / w.sum()


async def settle(seconds: float = 0.05) -> None:
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline:
        await asyncio.sleep(0.005)


async def until(pred, timeout_s: float, what: str) -> None:
    deadline = time.perf_counter() + timeout_s
    while not pred():
        if time.perf_counter() > deadline:
            raise SystemExit(f"EDGE PATH FAILED: timed out waiting for {what}")
        await asyncio.sleep(0.01)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"EDGE PATH FAILED: {what}")


class Edge:
    """One in-process edge gateway: own fusion graph + RpcHub + transport
    (codec-faithful) + EdgeNode + shared delivery observer (+ optional
    multi-process delivery pool)."""

    def __init__(
        self, i: int, server_rpc: RpcHub, wire_codec: bool,
        fan_workers: int = 2, value_plane: str = "block",
    ):
        self.i = i
        self.fusion = FusionHub()
        self.rpc = RpcHub(f"edge-{i}")
        install_compute_call_type(self.rpc)
        self.transport = RpcTestTransport(
            self.rpc, server_rpc, wire_codec=wire_codec, client_name=f"e{i}"
        )
        self.node = EdgeNode(
            "dag", self.rpc, self.fusion, name=f"edge-{i}",
            fan_workers=fan_workers,
            reread_batch=value_plane != "perkey",
            value_blocks=value_plane == "block",
        )
        self.observer = Observer()
        self.pool = None
        #: per-worker (subscriptions, baseline-deliveries) for the round
        #: accounting in pool mode
        self.worker_expected: list = []
        self.worker_base: list = []
        self.sim_subs = 0

    async def workers_done(self) -> tuple:
        """(done, delivered-so-far-this-round) against the armed
        baselines — one stats round trip per call (which also merges the
        workers' delivery histograms into the process registry)."""
        stats = await self.pool.stats()
        delivered = [
            s["deliveries"] - b for s, b in zip(stats, self.worker_base)
        ]
        done = all(
            d >= exp for d, exp in zip(delivered, self.worker_expected)
        )
        return done, sum(delivered)


async def main() -> None:
    from stl_fusion_tpu.graph import enable_program_cache, require_accelerator

    device = require_accelerator("perf/edge_path.py")
    enable_program_cache()
    n = int(os.environ.get("EDGE_GRAPH_NODES", 2_000_000))
    n_edges = int(os.environ.get("EDGE_NODES", 4))
    n_sessions = int(os.environ.get("EDGE_SESSIONS", 1_000_000))
    n_keys = int(os.environ.get("EDGE_KEYS", 512))
    keys_per_session = int(os.environ.get("EDGE_KEYS_PER_SESSION", 2))
    zipf_a = float(os.environ.get("EDGE_ZIPF", 1.1))
    rounds = int(os.environ.get("EDGE_ROUNDS", 2))
    n_groups = int(os.environ.get("EDGE_GROUPS", 16))
    seeds_per_group = int(os.environ.get("EDGE_SEEDS_PER_GROUP", 2))
    timeout_s = float(os.environ.get("EDGE_TIMEOUT_S", 600))
    wire_codec = os.environ.get("EDGE_WIRE", "1") == "1"
    smoke = os.environ.get("EDGE_SMOKE", "0") == "1"
    n_workers = int(os.environ.get("EDGE_WORKERS", 2))
    fan_workers = int(os.environ.get("EDGE_FAN_WORKERS", 2))
    value_plane = os.environ.get("EDGE_VALUE_PLANE", "block")
    accept_plane = os.environ.get("EDGE_ACCEPT_PLANE", "send_fds")
    require(
        value_plane in ("block", "batch", "perkey"),
        f"EDGE_VALUE_PLANE must be block|batch|perkey, got {value_plane!r}",
    )
    rng = np.random.default_rng(523)

    note(f"generating {n}-node power-law DAG...")
    src, dst = power_law_dag(n, avg_degree=3, seed=7)

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(
            hub, node_capacity=n + 64,
            edge_capacity=len(src) + max(65536, 8 * n_edges * n_keys * (rounds + 2)),
        )
        Dag = make_dag_service(n)
        svc = Dag(hub)
        hub.add_service(svc, "dag")
        table = memo_table_of(svc.node)

        note("columnar build + device warm...")
        t0 = time.perf_counter()
        block = backend.bind_table_rows(table)
        backend.declare_row_edges(block, src, block, dst)
        backend.warm_block_on_device(block)
        backend.flush()
        build_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        backend.graph.build_topo_mirror()
        mirror_s = time.perf_counter() - t0
        note(f"built in {build_s:.1f}s, mirror in {mirror_s:.1f}s")

        server_rpc = RpcHub("server")
        install_compute_call_type(server_rpc)
        server_rpc.add_service("dag", svc)
        from stl_fusion_tpu.rpc import install_compute_fanout, install_value_publisher

        fanout_index = install_compute_fanout(server_rpc, backend)
        publisher = None
        if value_plane == "block":
            publisher = install_value_publisher(server_rpc)

        # distinct keys: tail rows (shallow own-closures; the deep seeds
        # below give the wave its full-scale walk)
        key_rows = np.sort(
            n - 1 - rng.choice(n // 4, size=n_keys, replace=False)
        )
        key_specs = [("node", int(r)) for r in key_rows]

        # burst groups: every subscribed row round-robined across groups,
        # plus deep random seeds for the full-graph closure
        groups = [list() for _ in range(n_groups)]
        for j, r in enumerate(key_rows.tolist()):
            groups[j % n_groups].append(int(r))
        deep = rng.choice(n // 10, size=(n_groups, seeds_per_group), replace=False)
        for gi in range(n_groups):
            groups[gi].extend(int(s) for s in deep[gi])

        note("warming lane + refresh programs (untimed)...")
        t0 = time.perf_counter()
        backend.cascade_rows_lanes(block, groups)
        backend.refresh_block_on_device(block)
        backend.flush()
        note(f"programs warm ({time.perf_counter() - t0:.1f}s)")

        # ---------------------------------------------------------- edges
        rss_before = rss_mb()
        edges = [
            Edge(
                i, server_rpc, wire_codec, fan_workers=fan_workers,
                value_plane=value_plane,
            )
            for i in range(n_edges)
        ]
        if n_workers > 0:
            note(
                f"starting {n_workers} delivery workers per edge "
                f"({accept_plane} accept plane)..."
            )
            for e in edges:
                e.pool = await EdgeWorkerPool(
                    e.node, workers=n_workers, accept_plane=accept_plane
                ).start()
        note(f"subscribing {n_edges} edges × {n_keys} keys upstream...")
        t0 = time.perf_counter()
        # prime every edge's upstream subs by attaching one probe session
        # per edge over ALL keys (sessions proper ride the same subs)
        for e in edges:
            e.node.attach(key_specs, sink=e.observer.sink, track_versions=False)
        for e in edges:
            await until(
                lambda e=e: len(e.node._subs) == n_keys
                and all(s.version >= 1 for s in e.node._subs.values()),
                timeout_s, f"edge {e.i} upstream warm",
            )
        subscribe_s = time.perf_counter() - t0

        note(
            f"attaching {n_sessions} sessions (zipf a={zipf_a} over "
            f"{n_keys} keys, "
            + (f"{n_workers} worker procs/edge" if n_workers else "in-parent")
            + ")..."
        )
        t0 = time.perf_counter()
        weights = zipf_weights(n_keys, zipf_a)
        per_edge = n_sessions // n_edges
        sim_subs_total = 0
        for e in edges:
            picks = rng.choice(n_keys, size=(per_edge, keys_per_session), p=weights)
            if n_workers > 0:
                # sessions round-robin over the edge's worker processes;
                # each worker holds the per-session envelope prefixes, the
                # parent only the per-worker subscription COUNTS
                counts: list = [dict() for _ in range(n_workers)]
                for si, row in enumerate(picks):
                    c = counts[si % n_workers]
                    for k in set(row.tolist()):
                        spec = key_specs[k]
                        c[spec] = c.get(spec, 0) + 1
                e.worker_expected = []
                for w, cmap in enumerate(counts):
                    added = await e.pool.add_sim_sessions(w, cmap)
                    e.worker_expected.append(added)
                    sim_subs_total += added
                e.sim_subs = sum(e.worker_expected)
            else:
                sink = e.observer.sink
                attach = e.node.attach
                for row in picks:
                    specs = [key_specs[k] for k in set(row.tolist())]
                    attach(
                        specs, sink=sink, track_versions=False,
                        replay_current=False,
                    )
        attach_s = time.perf_counter() - t0
        rss_after = rss_mb()
        per_edge_rss_mb = (rss_after - rss_before) / n_edges
        parent_sessions = sum(len(e.node._sessions) for e in edges)
        total_sessions = parent_sessions + (
            per_edge * n_edges if n_workers > 0 else 0
        )
        parent_subs_per_round = sum(
            sub.session_count
            for e in edges
            for sub in e.node._subs.values()
        )
        expected_per_round = parent_subs_per_round + sim_subs_total
        note(
            f"attached in {attach_s:.1f}s; {total_sessions} sessions, "
            f"{expected_per_round} subscriptions, "
            f"{per_edge_rss_mb:.0f} MB/edge (parent)"
        )

        # ------------------------------------------------- invariant: ONE
        # upstream subscription per distinct key per edge, and the server
        # sees exactly edges×keys subscriptions — not sessions×keys
        for e in edges:
            require(
                len(e.node._subs) == n_keys,
                f"edge {e.i} holds {len(e.node._subs)} upstream subs, want {n_keys}",
            )
        await until(
            lambda: fanout_index.subscriptions == n_edges * n_keys,
            timeout_s, "server-side subscription registration",
        )

        # ---------------------------------------------------------- rounds
        hist = global_metrics().histogram(
            "fusion_edge_delivery_ms",
            help="server fence (wave apply) -> edge session client-visible",
        )
        fanout_s = 0.0
        burst_s = 0.0
        round_deliveries = 0
        delivery: dict = {}

        def upstream_counts():
            return {
                "rpcs": sum(e.node.upstream_rpcs for e in edges),
                "per_key": sum(e.node.per_key_rereads for e in edges),
                "batches": sum(e.node.reread_batches for e in edges),
                "block_hits": sum(e.node.block_hits for e in edges),
                "fences": sum(e.node.upstream_fences for e in edges),
            }

        # the FIRST-burst gate (ISSUE 11): the warm subscribe storm itself
        # must already ride the value plane — per-key re-read RPCs stay ≤
        # keys (batch/block modes run it as recompute_batch frames)
        warm = upstream_counts()
        if value_plane in ("batch", "block"):
            require(
                warm["per_key"] <= n_edges * n_keys,
                f"first-burst per-key re-reads {warm['per_key']} exceed "
                f"{n_edges * n_keys} keys — the batched path never engaged",
            )
            require(
                warm["batches"] >= n_edges,
                f"no recompute_batch frames on the warm subscribe "
                f"({warm['batches']})",
            )
        measured_base = warm
        prev_counts = warm
        for rnd in range(rounds):
            # all upstream subs re-registered (the previous round's fences
            # unindexed them until each edge's re-read landed)
            await until(
                lambda: fanout_index.subscriptions == n_edges * n_keys,
                timeout_s, f"round {rnd} re-subscription",
            )
            backend.flush()
            for e in edges:
                e.observer.arm(
                    sum(sub.session_count for sub in e.node._subs.values())
                )
                if e.pool is not None:
                    e.worker_base = [
                        s["deliveries"] for s in await e.pool.stats()
                    ]
            cp = hist.checkpoint()
            t0 = time.perf_counter()
            counts = backend.cascade_rows_lanes(block, groups)
            t_burst = time.perf_counter()
            await asyncio.wait_for(
                asyncio.gather(*(e.observer.event.wait() for e in edges)),
                timeout_s,
            )
            t_obs = time.perf_counter()
            worker_round = 0
            if n_workers > 0:
                # the worker processes reach their round quota in
                # parallel; each poll also merges the worker histograms
                # into the process delivery histogram
                deadline = time.perf_counter() + timeout_s
                pending = list(edges)
                while pending:
                    still = []
                    for e in pending:
                        done, delivered = await e.workers_done()
                        if not done:
                            still.append(e)
                    if still and time.perf_counter() > deadline:
                        raise SystemExit(
                            "EDGE PATH FAILED: timed out waiting for "
                            f"round {rnd} worker deliveries"
                        )
                    pending = still
                    if pending:
                        await asyncio.sleep(0.02)
                for e in edges:
                    _done, delivered = await e.workers_done()
                    worker_round += delivered
            t_all = time.perf_counter()
            burst_s += t_burst - t0
            fanout_s += t_all - t_burst
            round_total = sum(e.observer.fenced for e in edges) + worker_round
            round_deliveries += round_total
            delivery = hist.since(cp)  # last round's distribution
            now_counts = upstream_counts()
            note(
                f"round {rnd}: burst {t_burst - t0:.2f}s "
                f"({int(counts.sum()):,} inv), fan-out {t_all - t_burst:.2f}s "
                f"(upstream+probe {t_obs - t_burst:.2f}s, workers "
                f"{t_all - t_obs:.2f}s; {round_total:,} deliveries), "
                f"delivery p50/p99 {delivery['p50']}/{delivery['p99']} ms; "
                f"upstream rpcs +{now_counts['rpcs'] - prev_counts['rpcs']}, "
                f"block hits +{now_counts['block_hits'] - prev_counts['block_hits']}"
            )
            prev_counts = now_counts
            backend.refresh_block_on_device(block)
            backend.flush()
            await settle()

        # --------------------------------------- value-plane gates (ISSUE 11)
        final = upstream_counts()
        measured_rpcs = final["rpcs"] - measured_base["rpcs"]
        measured_per_key = final["per_key"] - measured_base["per_key"]
        measured_batches = final["batches"] - measured_base["batches"]
        measured_hits = final["block_hits"] - measured_base["block_hits"]
        measured_fences = final["fences"] - measured_base["fences"]
        if value_plane in ("batch", "block"):
            require(
                measured_per_key == 0,
                f"{measured_per_key} per-key upstream re-read RPCs re-entered "
                f"during the measured bursts — the value plane disengaged",
            )
        if value_plane == "block":
            # block-warm bursts: the block IS the fence + the value — any
            # upstream re-read round trip (batched included) fails the run
            require(
                measured_rpcs == 0,
                f"{measured_rpcs} upstream re-read RPCs on block-warm bursts "
                f"(want 0: every fence must be served from a wave block)",
            )
            require(
                measured_hits == n_edges * n_keys * rounds,
                f"block hits {measured_hits} != "
                f"{n_edges * n_keys * rounds} fences — some keys left the "
                f"value plane mid-run",
            )
            require(
                publisher is not None and publisher.stats()["fallback_fences"] == 0,
                "publisher fell back to plain fences "
                f"({publisher.stats()['fallback_fences'] if publisher else '?'})",
            )
        upstream_rpcs_per_burst = (
            round(measured_rpcs / rounds, 2) if rounds else None
        )
        block_hit_ratio = (
            round(measured_hits / measured_fences, 4) if measured_fences else None
        )
        total_batches = sum(e.node.reread_batches for e in edges)
        reread_batch_size = (
            round(sum(e.node.reread_batch_keys for e in edges) / total_batches, 1)
            if total_batches
            else None
        )
        note(
            f"value plane [{value_plane}]: measured bursts took "
            f"{measured_rpcs} upstream RPCs ({measured_per_key} per-key, "
            f"{measured_batches} batch frames), block hits {measured_hits}"
            f"/{measured_fences} fences"
        )

        worker_evictions = 0
        worker_rss = []
        deliveries_by_worker = []
        if n_workers > 0:
            for e in edges:
                for s in await e.pool.stats():
                    worker_evictions += s.get("evictions", 0)
                    worker_rss.append(s.get("rss_mb", 0.0))
                    deliveries_by_worker.append(s.get("deliveries", 0))
        evictions = sum(e.node.evictions for e in edges) + worker_evictions
        require(evictions == 0, f"{evictions} sessions were evicted mid-run")
        require(
            round_deliveries == expected_per_round * rounds,
            f"deliveries {round_deliveries} != expected {expected_per_round * rounds}",
        )

        # ---------------------------------------- amortization invariant
        # (ISSUE 10): encodes ≈ distinct fanned (key, version) pairs —
        # sub.version counts exactly the fanned versions per key — and
        # STRICTLY ≪ deliveries; any per-session encode re-entry explodes
        # frames_encoded past the version total and fails here
        frames_encoded_total = sum(e.node.frames_encoded for e in edges)
        versions_total = sum(
            sub.version for e in edges for sub in e.node._subs.values()
        )
        deliveries_total = sum(e.node.deliveries for e in edges) + sum(
            deliveries_by_worker
        )
        require(
            frames_encoded_total >= n_edges * n_keys,
            "serialize-once cache never engaged "
            f"(encodes {frames_encoded_total})",
        )
        require(
            frames_encoded_total <= versions_total + n_edges * n_keys,
            f"per-session encode re-entry: {frames_encoded_total} encodes "
            f"for {versions_total} fanned (key, version) pairs",
        )
        encode_ratio = (
            deliveries_total / frames_encoded_total if frames_encoded_total else 0.0
        )
        # the floor scales with the configured fan-out and caps at the
        # canonical 100 (ISSUE 10 acceptance at the zipf workload)
        ratio_floor = min(
            100.0, max(2.0, expected_per_round / (n_edges * n_keys * 2))
        )
        require(
            encode_ratio >= ratio_floor,
            f"encode ratio {encode_ratio:.1f} below floor {ratio_floor:.1f} "
            f"({deliveries_total} deliveries / {frames_encoded_total} encodes)",
        )

        smoke_result = None
        if smoke:
            smoke_result = await run_smoke(
                edges[0], n_edges * n_keys, fanout_index, backend, block, groups,
                timeout_s, [e.node for e in edges], value_plane,
            )

        result = {
            "metric": "edge_path",
            **device,
            "graph_nodes": n,
            "edges_graph": int(backend.edge_count),
            "edge_nodes": n_edges,
            "subscribers": total_sessions,
            "sessions_per_edge": per_edge,
            "distinct_keys": n_keys,
            "keys_per_session": keys_per_session,
            "zipf_a": zipf_a,
            "subscriptions": expected_per_round,
            "upstream_subs_per_edge": n_keys,
            "upstream_subs_total": n_edges * n_keys,
            "rounds": rounds,
            "wire_codec": wire_codec,
            "edge_workers": n_workers,
            "fan_workers": fan_workers,
            "accept_plane": accept_plane if n_workers else None,
            # the upstream value plane (ISSUE 11)
            "value_plane": value_plane,
            "upstream_rpcs_per_burst": upstream_rpcs_per_burst,
            "block_hit_ratio": block_hit_ratio,
            "reread_batch_size": reread_batch_size,
            "upstream_rpcs_total": final["rpcs"],
            "per_key_rereads_total": final["per_key"],
            "reread_fallbacks": sum(e.node.reread_fallbacks for e in edges),
            "block_hits_total": final["block_hits"],
            "publisher": publisher.stats() if publisher is not None else None,
            "frames_encoded": frames_encoded_total,
            "deliveries_total": deliveries_total,
            "encode_ratio": round(encode_ratio, 1),
            "build_s": round(build_s, 2),
            "mirror_build_s": round(mirror_s, 2),
            "subscribe_s": round(subscribe_s, 2),
            "attach_s": round(attach_s, 2),
            "attach_sessions_per_s": round(total_sessions / attach_s, 0) if attach_s else None,
            "burst_s": round(burst_s, 3),
            "fanout_s": round(fanout_s, 3),
            "fenced_total": round_deliveries,
            "fenced_per_s": round(round_deliveries / fanout_s, 1) if fanout_s else None,
            "deliveries_per_s_per_worker": round(
                round_deliveries / fanout_s / (n_edges * n_workers), 1
            )
            if fanout_s and n_workers
            else None,
            # the system's own fence→client-visible histogram (last round)
            "delivery_ms_p50": delivery.get("p50"),
            "delivery_ms_p99": delivery.get("p99"),
            "system_delivery_ms": delivery,
            "per_edge_rss_mb": round(per_edge_rss_mb, 1),
            "per_worker_rss_mb": round(
                sum(worker_rss) / len(worker_rss), 1
            )
            if worker_rss
            else None,
            "evictions": evictions,
            "coalesced_frames": sum(e.node.coalesced_frames for e in edges),
        }
        if smoke_result is not None:
            result["smoke"] = smoke_result
        print(json.dumps(result))
        note("done")
        for e in edges:
            await e.node.close()
            await e.rpc.stop()
        await server_rpc.stop()
    finally:
        set_default_hub(old)


async def run_smoke(
    edge: "Edge", expected_upstream_total: int, fanout_index, backend, block,
    groups, timeout_s: float, all_nodes=None, value_plane: str = "block",
) -> dict:
    """EDGE_SMOKE=1 (tier1.yml): boot a REAL EdgeHttpServer on the first
    edge, attach live SSE consumers over TCP (plus a WebSocket consumer
    when the optional ``websockets`` package is installed — the WS load
    leg), burst once, and assert the `/metrics` exposition shows the tier
    working: fusion_edge_sessions, a non-empty delivery histogram,
    upstream subscriptions == distinct keys (coalescing actually engaged,
    not N× fan-in), and the ISSUE 11 value-plane gate (block mode: block
    hits present, zero per-key re-entry on the block-served burst)."""
    import urllib.parse

    from stl_fusion_tpu.edge import EdgeHttpServer

    node = edge.node
    http = await EdgeHttpServer(node, heartbeat_interval=5.0).start()
    note(f"smoke: SSE server at {http.url}")
    key_specs = [
        (sub.method, *sub.args) for sub in list(node._subs.values())[:2]
    ]
    keys_q = urllib.parse.quote(json.dumps([list(k) for k in key_specs]))
    try:
        import websockets  # noqa: F401 — optional: the WS load leg
        has_websockets = True
    except ImportError:
        has_websockets = False
        note("smoke: websockets not installed — WS leg skipped")
    ws_server = None
    ws_conn = None
    if has_websockets:
        from websockets.asyncio.client import connect as ws_connect

        from stl_fusion_tpu.edge import EdgeWebSocketServer

        ws_server = await EdgeWebSocketServer(
            node, heartbeat_interval=5.0
        ).start()
        note(f"smoke: WS server at {ws_server.url}")
        ws_conn = await ws_connect(ws_server.url)
        await ws_conn.send(json.dumps({"keys": [list(k) for k in key_specs]}))
        ws_hello = json.loads(await asyncio.wait_for(ws_conn.recv(), 30.0))
        require("hello" in ws_hello, f"smoke: bad WS hello {ws_hello}")
        ws_replay = json.loads(await asyncio.wait_for(ws_conn.recv(), 30.0))
        require(
            len(ws_replay.get("frames", [])) >= 1,
            f"smoke: WS replay missing ({ws_replay})",
        )
    readers = []
    for _ in range(2):
        reader, writer = await asyncio.open_connection(http.host, http.port)
        writer.write(
            f"GET /edge/sse?keys={keys_q} HTTP/1.1\r\nHost: x\r\n\r\n".encode()
        )
        await writer.drain()
        while True:
            line = (await asyncio.wait_for(reader.readline(), 30.0)).decode()
            require(line != "", "smoke: SSE connection closed during headers")
            if line in ("\r\n", "\n"):
                break
        readers.append((reader, writer))

    async def read_event(reader):
        fields = {}
        while True:
            line = (await asyncio.wait_for(reader.readline(), 30.0)).decode()
            require(line != "", "smoke: SSE stream closed early")
            if line in ("\n", "\r\n"):
                if fields:
                    return fields
                continue
            if line.startswith(":"):
                continue
            name, _, value = line.rstrip("\n").partition(":")
            fields[name] = value.strip()

    for reader, _w in readers:
        hello = await read_event(reader)
        require(hello.get("event") == "hello", f"smoke: bad hello {hello}")
        for _ in key_specs:
            ev = await read_event(reader)  # initial values
            require(ev.get("event") == "update", f"smoke: bad initial {ev}")

    # the measured rounds' fences unindexed every subscription until each
    # edge's re-read landed: wait for full re-registration (the round
    # loop's own guard) or the smoke burst can miss a still-unindexed key
    await until(
        lambda: fanout_index.subscriptions == expected_upstream_total,
        timeout_s, "smoke re-subscription",
    )
    backend.flush()
    per_key_before = sum(nd.per_key_rereads for nd in (all_nodes or [node]))
    rpcs_before = sum(nd.upstream_rpcs for nd in (all_nodes or [node]))
    backend.cascade_rows_lanes(block, groups)
    seen = []
    for reader, _w in readers:
        ev = await read_event(reader)
        require(ev.get("event") == "update", f"smoke: bad update {ev}")
        seen.append(json.loads(ev["data"]))
    require(all("t0" in d for d in seen), "smoke: frames lost the origin timestamp")
    ws_update_frames = None
    if ws_conn is not None:
        # the WS leg sees the same burst (frames batches; skip pings)
        deadline = time.perf_counter() + 30.0
        while ws_update_frames is None:
            require(
                time.perf_counter() < deadline, "smoke: WS update never arrived"
            )
            msg = json.loads(await asyncio.wait_for(ws_conn.recv(), 30.0))
            frames = msg.get("frames")
            if frames and any(f.get("t0") is not None for f in frames):
                ws_update_frames = len(frames)
    # ISSUE 11 smoke gate: per-key re-reads never re-enter on a
    # block-served burst; in batch/block modes the CUMULATIVE per-key
    # total stays ≤ keys (fallback slack only — the perkey A/B mode
    # legitimately accumulates ~keys per burst and is exempt)
    nodes_for_gate = all_nodes or [node]
    per_key_after = sum(nd.per_key_rereads for nd in nodes_for_gate)
    if value_plane != "perkey":
        require(
            per_key_after <= expected_upstream_total,
            f"smoke: {per_key_after} per-key re-reads exceed the "
            f"{expected_upstream_total} distinct-key total",
        )
    if value_plane == "block":
        require(
            per_key_after == per_key_before,
            f"smoke: {per_key_after - per_key_before} per-key re-read(s) "
            f"re-entered on a block-served burst",
        )
        await until(
            lambda: sum(nd.block_hits for nd in nodes_for_gate) > 0,
            30.0, "smoke: value-block hits",
        )
        require(
            sum(nd.upstream_rpcs for nd in nodes_for_gate) == rpcs_before,
            "smoke: upstream re-read RPCs on a block-served burst",
        )

    # scrape /metrics over real HTTP and assert the exposition
    reader, writer = await asyncio.open_connection(http.host, http.port)
    writer.write(b"GET /metrics HTTP/1.1\r\nHost: x\r\n\r\n")
    await writer.drain()
    raw = await asyncio.wait_for(reader.read(), 30.0)
    writer.close()
    text = raw.decode("utf-8", "replace")
    metrics = {}
    for line in text.splitlines():
        if line.startswith("fusion_edge_"):
            name, _, value = line.partition(" ")
            try:
                metrics[name] = float(value)
            except ValueError:
                pass
    sessions = metrics.get("fusion_edge_sessions", 0)
    subs = metrics.get("fusion_edge_upstream_subscriptions", 0)
    require(sessions >= 1, f"smoke: fusion_edge_sessions missing ({metrics})")
    require(
        metrics.get("fusion_edge_delivery_ms_count", 0) > 0,
        "smoke: edge delivery histogram is empty",
    )
    # all edges in this process export into one registry: the scrape's
    # total must equal edges × distinct keys — never sessions × keys
    require(
        subs == expected_upstream_total,
        f"smoke: upstream subscriptions {subs} != distinct-key total "
        f"{expected_upstream_total} — coalescing not engaged",
    )
    # the ISSUE 10 amortization invariant, asserted from the EXPOSITION
    # (what an operator's scrape would show): encodes present, bounded by
    # the fanned version totals (no per-session encode re-entry), and
    # strictly below the delivery total
    enc = metrics.get("fusion_edge_frames_encoded_total", 0)
    # worker deliveries ride the same encodes — the collector exports the
    # pool's last-pulled cumulative beside the parent's own count
    deliv = metrics.get("fusion_edge_deliveries_total", 0) + metrics.get(
        "fusion_edge_worker_deliveries_total", 0
    )
    # the scrape sums every edge node in the process: the version bound
    # must span them all too
    nodes = all_nodes if all_nodes is not None else [node]
    versions_total = sum(
        sub.version for nd in nodes for sub in nd._subs.values()
    )
    subs_slack = sum(len(nd._subs) for nd in nodes)
    require(enc > 0, "smoke: fusion_edge_frames_encoded_total missing/zero")
    require(
        enc <= versions_total + subs_slack,
        f"smoke: per-session encode re-entry — {enc} encodes for "
        f"{versions_total} fanned (key, version) pairs",
    )
    require(
        deliv >= 2 * enc,
        f"smoke: encode amortization not engaged — {deliv} deliveries "
        f"vs {enc} encodes",
    )
    smoke_workers = None
    if edge.pool is not None:
        # one REAL consumer through the SO_REUSEPORT worker listener: the
        # multi-process plane serves hello + the cached replay end to end
        port = await edge.pool.listen()
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        writer.write(
            f"GET /edge/sse?keys={keys_q} HTTP/1.1\r\nHost: x\r\n\r\n".encode()
        )
        await writer.drain()
        while True:
            line = (await asyncio.wait_for(reader.readline(), 30.0)).decode()
            require(line != "", "smoke: worker SSE closed during headers")
            if line in ("\r\n", "\n"):
                break
        hello = await read_event(reader)
        require(
            hello.get("event") == "hello", f"smoke: bad worker hello {hello}"
        )
        replays = [await read_event(reader) for _ in key_specs]
        require(
            all(ev.get("event") == "update" for ev in replays),
            f"smoke: bad worker replay {replays}",
        )
        require(
            all("t0" not in json.loads(ev["data"]) for ev in replays),
            "smoke: worker replay leaked the stale fence origin_ts",
        )
        writer.close()
        stats = await edge.pool.stats()
        smoke_workers = {
            "workers": len(stats),
            "worker_deliveries": sum(s["deliveries"] for s in stats),
            "listen_port": port,
        }
    for _r, w in readers:
        w.close()
    if ws_conn is not None:
        await ws_conn.close()
    if ws_server is not None:
        await ws_server.stop()
    await http.stop()
    out = {
        "sse_consumers": len(readers),
        "ws_consumers": 1 if ws_update_frames is not None else 0,
        "ws_update_frames": ws_update_frames,
        "value_plane": value_plane,
        "block_hits": sum(nd.block_hits for nd in (all_nodes or [node])),
        "per_key_rereads": sum(
            nd.per_key_rereads for nd in (all_nodes or [node])
        ),
        "metrics_sessions": sessions,
        "metrics_upstream_subs": subs,
        "delivery_count": metrics.get("fusion_edge_delivery_ms_count"),
        "frames_encoded": enc,
        "deliveries": deliv,
    }
    if smoke_workers is not None:
        out["worker_pool"] = smoke_workers
    return out


if __name__ == "__main__":
    asyncio.run(main())
