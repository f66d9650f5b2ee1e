#!/usr/bin/env python
"""Adversarial production-traffic harness (ISSUE 12 / ROADMAP item 4).

Every other perf script measures ONE steady-state shape; this one drives
the FULL stack — cluster-routed servers (two members behind a shard map),
the mesh-capable TpuGraphBackend bursting real device waves, EdgeNode
gateways behind AdmissionControllers, and the multi-process delivery
worker pool — through the traffic shapes that actually kill serving
systems, and FAILS (nonzero exit) on any SLO violation, so it doubles as
a CI gate:

1. **zipf hot-set migration** — the popular keys CHANGE mid-run: phase A
   bursts the zipf head, phase B the tail half; delivery p99 must hold
   through the migration.
2. **flash crowd** — TRAFFIC_FLASH subscribers arrive in seconds on ONE
   key through admission control: every arrival is ADMITTED OR SHED
   (counted — harness tally must equal the controller's counters),
   priority-tenant ("gold") shed rate must not exceed the anonymous
   rate, zero evictions of healthy admitted sessions, fan queues drain
   back to empty (no unbounded growth), and the post-crowd burst meets
   the delivery p99 ceiling.
3. **mass-reconnect storm** — park thousands of sessions, fence while
   they are away, then replay every resume token at once through the
   RESERVED resume lane: zero resume-lane sheds, every resumed session
   observes the value it missed, within the storm SLO.
4. **rolling edge restart** — graceful drain mid-traffic: the drained
   edge hints every live session (reconnect frame carrying its resume
   token), parks state, exports it; a successor node imports the parked
   state and every session resumes — the gate is ZERO deliveries lost
   (every (session, key) converges to the oracle despite the fences
   that landed during the restart gap; resume replay covers it).
5. **reshard mid-flash-crowd** — a second crowd arrives WHILE the shard
   map moves ~half the keys to a second member: moved keys re-pin, the
   single-upstream invariant holds, and the post-reshard burst converges
   oracle-clean within the p99 ceiling.
6. **write-path burst** (ISSUE 20) — commands drive the graph: order
   commands route through the ClusterCommander, the invalidation replay
   is COLLECTED and submitted through the nonblocking WavePipeline
   (command waves fuse — zero eager fallbacks), and the subscribed
   sessions see the fences within the command→visible ceiling; a
   duplicate operation id is absorbed (never re-applied).

Cross-cutting gates: the per-tenant SLO table (gold p99 ceiling at least
as tight as anonymous), a final ConsistencyAuditor sweep (zero invariant
violations — "staleness-auditor clean"), and shed/drain work COUNTED in
``fusion_edge_admitted_total``/``fusion_edge_shed_total{reason=}``/
``fusion_edge_drains_total`` — never silent.

TRAFFIC_SMOKE=1 (tier1.yml): one flash-crowd round + one drain round at
tiny scale — asserts shed counting, zero lost deliveries across the
drain, and exercises the SLO gate machinery end to end.

Env: TRAFFIC_SMOKE (0), TRAFFIC_GRAPH_NODES (200_000; smoke 20_000),
TRAFFIC_EDGES (2), TRAFFIC_KEYS (64; smoke 16), TRAFFIC_SESSIONS
(20_000; smoke 400), TRAFFIC_FLASH (100_000; smoke 2_000),
TRAFFIC_RECONNECT (10_000), TRAFFIC_KEYS_PER_SESSION (2), TRAFFIC_ZIPF
(1.1), TRAFFIC_WORKERS (2; the delivery-pool leg on edge 0),
TRAFFIC_CONNECT_RATE (2000), TRAFFIC_CONNECT_BURST (1000),
TRAFFIC_P99_MS (20_000), TRAFFIC_GOLD_P99_MS (= TRAFFIC_P99_MS),
TRAFFIC_RECONNECT_SLO_S (60), TRAFFIC_TIMEOUT_S (600), TRAFFIC_WIRE (1).

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
from stl_fusion_tpu.cluster import ShardMap, ShardMapRouter  # noqa: E402
from stl_fusion_tpu.core import (  # noqa: E402
    ComputeService,
    FusionHub,
    TableBacking,
    compute_method,
    memo_table_of,
    set_default_hub,
)
from stl_fusion_tpu.diagnostics.auditor import ConsistencyAuditor  # noqa: E402
from stl_fusion_tpu.edge import (  # noqa: E402
    DRAIN_KEY,
    AdmissionController,
    AdmissionRejected,
    EdgeNode,
    EdgeWorkerPool,
)
from stl_fusion_tpu.ext.multitenancy import (  # noqa: E402
    Tenant,
    TenantRegistry,
)
from stl_fusion_tpu.graph import TpuGraphBackend  # noqa: E402
from stl_fusion_tpu.graph.synthetic import power_law_dag  # noqa: E402
from stl_fusion_tpu.rpc import RpcHub, install_compute_fanout  # noqa: E402
from stl_fusion_tpu.rpc.testing import RpcMultiServerTestTransport  # noqa: E402
from stl_fusion_tpu.utils.serialization import wire_type  # noqa: E402


@wire_type("TrafficOrder")
@dataclasses.dataclass(frozen=True)
class OrderCmd:
    """S6's write: one order against a DAG row's cart. Routed by row so
    the command plane and the graph agree on the key."""

    row: int
    qty: int

    def shard_key(self):
        return f"row-{self.row}"


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"TRAFFIC PATH FAILED: {what}")


async def until(pred, timeout_s: float, what: str) -> None:
    deadline = time.perf_counter() + timeout_s
    while not pred():
        if time.perf_counter() > deadline:
            raise SystemExit(f"TRAFFIC PATH FAILED: timed out waiting for {what}")
        await asyncio.sleep(0.01)


async def settle(seconds: float = 0.05) -> None:
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline:
        await asyncio.sleep(0.005)


class SloGate:
    """The per-tenant SLO gate table: every check is RECORDED (pass or
    fail) so the JSON line shows the whole table, and enforce() fails the
    run — nonzero exit, CI-gate semantics — if any check failed.

    Pass/fail is delegated to ``SloSpec.violated`` — the same comparator
    the ``/health`` burn-rate engine uses — so a CI gate and a live
    health verdict can never disagree about what "violated" means."""

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
                "TRAFFIC PATH FAILED: SLO violations: "
                + "; ".join(
                    f"{c['name']}={c['value']} (ceiling {c['ceiling']})"
                    for c in failed
                )
            )


def make_dag_service(n: int):
    class DagTable(ComputeService):
        """The traffic DAG: row i's value is base[i] — the harness bumps
        ``base`` by one per burst GENERATION, so every fence carries a
        value that proves WHICH generation a session last saw (the
        zero-loss and staleness audits read it back)."""

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


def zipf_weights(n: int, a: float) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    w = 1.0 / ranks**a
    return w / w.sum()


class RoundCounter:
    """Per-edge delivery counter for one measured burst: counts fence
    frames (t0 present), collects fence→visible deltas per tenant."""

    def __init__(self):
        self.fenced = 0
        self.expected = 0
        self.event = asyncio.Event()
        self.deltas = {}  # tenant -> [ms]
        self.collect = False

    def arm(self, expected: int, collect: bool = True) -> None:
        self.fenced = 0
        self.expected = expected
        self.collect = collect
        for lst in self.deltas.values():
            lst.clear()
        self.event.clear()
        if expected <= 0:  # a drained/empty edge has nothing to wait for
            self.event.set()

    def hit(self, frame, tenant: str = "") -> None:
        t0 = frame[4]
        if t0 is None:
            return
        self.fenced += 1
        if self.collect:
            self.deltas.setdefault(tenant, []).append(
                (time.perf_counter() - t0) * 1e3
            )
        if self.fenced >= self.expected:
            self.event.set()


def pctile(values, q: float):
    if not values:
        return None
    arr = np.asarray(values, dtype=np.float64)
    return round(float(np.percentile(arr, q)), 1)


class Edge:
    """One edge gateway under test: shard-map-routed multi-server
    transport, an AdmissionController with the harness knobs, and the
    shared last-seen map the audits read."""

    def __init__(self, i, servers, wire, registry, knobs):
        self.i = i
        self.rpc = RpcHub(f"edge-{i}")
        install_compute_call_type(self.rpc)
        self.transport = RpcMultiServerTestTransport(
            self.rpc, servers, wire_codec=wire, client_name=f"e{i}"
        )
        self.router = ShardMapRouter(
            self.rpc, shard_map=ShardMap.initial(["s0"], epoch=1)
        )
        self.admission = AdmissionController(
            registry=registry,
            connect_rate=knobs["connect_rate"],
            connect_burst=knobs["connect_burst"],
            subscribe_rate=knobs["connect_rate"] * 4,
            subscribe_burst=knobs["connect_burst"] * 4,
            resume_rate=knobs["resume_rate"],
            resume_burst=knobs["resume_burst"],
            max_concurrent=knobs["max_concurrent"],
            name=f"edge-{i}",
        )
        self.node = EdgeNode(
            "dag", self.rpc, router=self.router, name=f"edge-{i}",
            fan_workers=2, reread_batch=True, value_blocks=False,
            admission=self.admission, resume_ttl=120.0,
        )
        self.counter = RoundCounter()
        self.pool = None
        self.sim_by_key = {}  # key spec -> sim session count (worker leg)
        self.worker_base = 0

    def make_sink(self, last: dict, sid, tenant: str = ""):
        counter = self.counter
        edge_i = self.i

        def sink(frame):
            last[(edge_i, sid, frame[0])] = frame
            counter.hit(frame, tenant)

        return sink


async def main() -> None:
    from stl_fusion_tpu.graph import enable_program_cache, require_accelerator

    device = require_accelerator("perf/traffic_path.py")
    enable_program_cache()
    smoke = os.environ.get("TRAFFIC_SMOKE", "0") == "1"

    def env_int(name, full, small):
        return int(os.environ.get(name, small if smoke else full))

    n = env_int("TRAFFIC_GRAPH_NODES", 200_000, 20_000)
    n_edges = env_int("TRAFFIC_EDGES", 2, 2)
    n_keys = env_int("TRAFFIC_KEYS", 64, 16)
    n_sessions = env_int("TRAFFIC_SESSIONS", 20_000, 400)
    flash_n = env_int("TRAFFIC_FLASH", 100_000, 2_000)
    reconnect_n = env_int("TRAFFIC_RECONNECT", 10_000, 200)
    keys_per_session = int(os.environ.get("TRAFFIC_KEYS_PER_SESSION", 2))
    zipf_a = float(os.environ.get("TRAFFIC_ZIPF", 1.1))
    n_workers = env_int("TRAFFIC_WORKERS", 2, 2)
    timeout_s = float(os.environ.get("TRAFFIC_TIMEOUT_S", 600))
    wire = os.environ.get("TRAFFIC_WIRE", "1") == "1"
    p99_ceiling = float(os.environ.get("TRAFFIC_P99_MS", 20_000))
    gold_ceiling = float(os.environ.get("TRAFFIC_GOLD_P99_MS", p99_ceiling))
    reconnect_slo_s = float(os.environ.get("TRAFFIC_RECONNECT_SLO_S", 60))
    # default admission knobs DERIVED from the crowd size so the flash
    # crowd structurally overloads the buckets on any box speed (the shed
    # path must engage for the counting gates): per-edge capacity over a
    # t-second arrival is rate*(1+t) ≈ flash/(20*edges)*(1+t), well under
    # the flash/(2*edges) anonymous arrivals for any realistic t
    default_rate = max(50.0, flash_n / (20.0 * n_edges))
    knobs = {
        "connect_rate": float(
            os.environ.get("TRAFFIC_CONNECT_RATE", default_rate)
        ),
        "connect_burst": float(
            os.environ.get("TRAFFIC_CONNECT_BURST", default_rate)
        ),
        "resume_rate": 50_000.0,
        "resume_burst": 50_000.0,
        "max_concurrent": 4096,
    }
    rng = np.random.default_rng(1217)
    slo = SloGate()

    note(f"generating {n}-node power-law DAG...")
    src, dst = power_law_dag(n, avg_degree=3, seed=7)

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(
            hub, node_capacity=n + 64, edge_capacity=len(src) + 262144,
        )
        Dag = make_dag_service(n)
        svc = Dag(hub)
        hub.add_service(svc, "dag")
        table = memo_table_of(svc.node)
        base0 = svc.base.copy()

        note("columnar build + device warm...")
        block = backend.bind_table_rows(table)
        backend.declare_row_edges(block, src, block, dst)
        backend.warm_block_on_device(block)
        backend.flush()
        backend.graph.build_topo_mirror()

        # -- the cluster: two serving members behind one shard map ------
        servers = {}
        fanouts = {}
        for ref in ("s0", "s1"):
            rpc = RpcHub(ref)
            install_compute_call_type(rpc)
            rpc.add_service("dag", svc)
            fanouts[ref] = install_compute_fanout(rpc, backend)
            servers[ref] = rpc

        # -- tenants: gold rides the priority lane ----------------------
        registry = TenantRegistry(single_tenant=False)
        registry.add(Tenant("gold", title="paying", priority=True))
        registry.add(Tenant("free", title="free tier"))

        # -- keys: tail rows (shallow closures — the burst fences the
        # subscribed rows, not half the graph)
        key_rows = np.sort(
            n - 1 - rng.choice(n // 4, size=n_keys, replace=False)
        ).tolist()
        key_specs = [("node", int(r)) for r in key_rows]
        spec_of_row = {r: s for r, s in zip(key_rows, key_specs)}

        note("warming lane + refresh programs (untimed)...")
        warm_groups = [
            [int(x) for x in chunk]
            for chunk in np.array_split(np.asarray(key_rows), 8)
        ]
        backend.cascade_rows_lanes(block, warm_groups)
        backend.refresh_block_on_device(block)
        backend.flush()

        edges = [Edge(i, servers, wire, registry, knobs) for i in range(n_edges)]

        # -- generation machinery: every burst bumps the value plane so
        # audits can read back WHICH generation a session last saw
        gen = {"v": 0}

        def oracle(row: int) -> float:
            return float(row + gen["v"])

        async def burst(rows, collect=True, wait_timeout=None) -> None:
            """One generation: bump values, fence ``rows``, wait for every
            edge's expected deliveries, refresh the device table.
            ``wait_timeout`` bounds the wait WITHOUT failing (the
            background-traffic mode during a drain: a burst armed just
            before sessions parked can legitimately never complete —
            convergence is the final audit's job, not this wait's)."""
            gen["v"] += 1
            svc.base = base0 + np.float32(gen["v"])
            svc._base_dev = None
            fenced_keys = {
                edges[0].node.key_str(spec_of_row[r])
                for r in rows if r in spec_of_row
            }
            for e in edges:
                expected = sum(
                    sub.session_count
                    for ks, sub in e.node._subs.items()
                    if ks in fenced_keys
                )
                e.counter.arm(expected, collect=collect)
                if e.pool is not None:
                    e.worker_base = sum(
                        s["deliveries"] for s in await e.pool.stats()
                    )
            groups = [
                [int(x) for x in chunk]
                for chunk in np.array_split(
                    np.asarray(rows), max(1, min(8, len(rows)))
                )
            ]
            backend.cascade_rows_lanes(block, groups)
            bound = timeout_s if wait_timeout is None else wait_timeout
            try:
                await asyncio.wait_for(
                    asyncio.gather(*(e.counter.event.wait() for e in edges)),
                    bound,
                )
                for e in edges:
                    if e.pool is None:
                        continue
                    exp_sim = sum(
                        count for spec, count in e.sim_by_key.items()
                        if e.node.key_str(spec) in fenced_keys
                    )
                    if exp_sim:
                        async def sim_done(e=e, exp=exp_sim):
                            got = sum(
                                s["deliveries"] for s in await e.pool.stats()
                            ) - e.worker_base
                            return got >= exp

                        deadline = time.perf_counter() + bound
                        while not await sim_done():
                            require(
                                time.perf_counter() < deadline
                                or wait_timeout is not None,
                                "worker-pool sim deliveries timed out",
                            )
                            if time.perf_counter() >= deadline:
                                break
                            await asyncio.sleep(0.02)
            except asyncio.TimeoutError:
                require(
                    wait_timeout is not None,
                    "burst deliveries timed out",
                )
            backend.refresh_block_on_device(block)
            backend.flush()

        def quiesced() -> bool:
            """No unbounded queue growth: fan shards drained, no gate holds."""
            return all(
                not any(s._pending for s in e.node._fan_shards)
                and e.node.admission.in_flight == 0
                for e in edges
            )

        # -- base population: the pre-existing steady state (attached as
        # already-admitted — the ADVERSARIAL arrivals below are what ride
        # admission), zipf over the keys, 10% gold / 30% free / 60% anon
        note(f"attaching {n_sessions} base sessions (zipf a={zipf_a})...")
        last: dict = {}
        weights = zipf_weights(n_keys, zipf_a)
        base_sessions = []  # (edge, sid, tenant, session)
        per_edge = n_sessions // n_edges
        for e in edges:
            picks = rng.choice(
                n_keys, size=(per_edge, keys_per_session), p=weights
            )
            for si, row in enumerate(picks):
                sid = f"b{si}"
                tenant = "gold" if si % 10 == 0 else ("free" if si % 10 < 4 else "")
                specs = [key_specs[k] for k in set(row.tolist())]
                session = e.node.attach(
                    specs, sink=e.make_sink(last, sid, tenant),
                    replay_current=False, admitted=True,
                )
                base_sessions.append((e, sid, tenant, session))
        for e in edges:
            await until(
                lambda e=e: all(s.version >= 1 for s in e.node._subs.values())
                if e.node._subs else False,
                timeout_s, f"edge {e.i} upstream warm",
            )

        # -- the delivery worker pool leg (edge 0): sim sessions served
        # by OS worker processes ride the same bursts throughout
        if n_workers > 0:
            note(f"starting {n_workers} delivery workers on edge 0...")
            e0 = edges[0]
            e0.pool = await EdgeWorkerPool(e0.node, workers=n_workers).start()
            sim_total = max(100, n_sessions // 10)
            counts = {key_specs[0]: sim_total // 2}
            for k in range(1, min(4, n_keys)):
                counts[key_specs[k]] = sim_total // 8
            for w in range(n_workers):
                await e0.pool.add_sim_sessions(
                    w, {s: max(1, c // n_workers) for s, c in counts.items()}
                )
            e0.sim_by_key = {
                s: max(1, c // n_workers) * n_workers for s, c in counts.items()
            }

        upstream_total = sum(len(e.node._subs) for e in edges)
        results: dict = {"metric": "traffic_path", **device, "smoke": smoke,
                         "graph_nodes": n, "edge_nodes": n_edges,
                         "distinct_keys": n_keys, "base_sessions": n_sessions,
                         "workers": n_workers}

        # ========================================================== S1
        # zipf hot-set migration (full runs): the popular half bursts,
        # then popularity MIGRATES to the tail half
        if not smoke:
            note("S1: zipf hot-set migration...")
            head = key_rows[: n_keys // 2]
            tail = key_rows[n_keys // 2:]
            await burst(head)
            p99_a = pctile(
                [d for e in edges for lst in e.counter.deltas.values() for d in lst],
                99,
            )
            await burst(tail)
            p99_b = pctile(
                [d for e in edges for lst in e.counter.deltas.values() for d in lst],
                99,
            )
            slo.check("zipf.head_p99", p99_a, p99_ceiling)
            slo.check("zipf.migrated_p99", p99_b, p99_ceiling)
            results["zipf"] = {"head_p99_ms": p99_a, "migrated_p99_ms": p99_b}
            await until(quiesced, timeout_s, "S1 queue drain")

        # ========================================================== S2
        # flash crowd: flash_n arrivals on ONE key in seconds, through
        # admission — counted shed, lane fairness, bounded queues
        note(f"S2: flash crowd ({flash_n} arrivals on one hot key)...")
        hot_spec = key_specs[0]
        adm_before = [e.admission.snapshot() for e in edges]
        attempts = {"gold": 0, "anon": 0}
        admitted = {"gold": 0, "anon": 0}
        shed = {"gold": 0, "anon": 0}
        flash_sessions = []
        t0 = time.perf_counter()
        for j in range(flash_n):
            e = edges[j % n_edges]
            tenant = "gold" if j % 10 == 0 else ""
            lane = "gold" if tenant else "anon"
            attempts[lane] += 1
            try:
                s = e.node.attach(
                    [hot_spec], sink=e.make_sink(last, f"f{j}", tenant),
                    track_versions=False, replay_current=False, tenant=tenant,
                )
                admitted[lane] += 1
                flash_sessions.append((e, f"f{j}", s))
            except AdmissionRejected:
                shed[lane] += 1
            if j % 256 == 255:
                await asyncio.sleep(0)  # the loop (and refills) breathe
        arrival_s = time.perf_counter() - t0
        note(
            f"  crowd arrived in {arrival_s:.2f}s: admitted {admitted}, "
            f"shed {shed}"
        )
        # accounting: harness tally == controller counters, exactly
        adm_after = [e.admission.snapshot() for e in edges]
        ctrl_admitted = sum(
            sum(a["admitted"].values()) - sum(b["admitted"].values())
            for a, b in zip(adm_after, adm_before)
        )
        ctrl_shed = sum(
            sum(a["shed"].values()) - sum(b["shed"].values())
            for a, b in zip(adm_after, adm_before)
        )
        require(
            admitted["gold"] + admitted["anon"] == ctrl_admitted,
            f"admitted tally {admitted} != controller count {ctrl_admitted}",
        )
        require(
            shed["gold"] + shed["anon"] == ctrl_shed,
            f"shed tally {shed} != controller count {ctrl_shed}",
        )
        require(
            sum(attempts.values())
            == sum(admitted.values()) + sum(shed.values()),
            "admitted + shed != attempts",
        )
        require(
            sum(shed.values()) > 0,
            "the flash crowd never overloaded admission — raise "
            "TRAFFIC_FLASH or lower TRAFFIC_CONNECT_BURST",
        )
        require(sum(admitted.values()) > 0, "admission shed EVERY arrival")
        gold_rate = shed["gold"] / max(1, attempts["gold"])
        anon_rate = shed["anon"] / max(1, attempts["anon"])
        slo.check("flash.gold_shed_rate_vs_anon", round(gold_rate, 4),
                  round(anon_rate, 4), unit="rate")
        # the post-crowd burst: the admitted crowd (+ the base population
        # on the hot key) must see the fence within the ceiling
        evictions_before = sum(e.node.evictions for e in edges)
        await burst([key_rows[0]])
        flash_deltas = [
            d for e in edges for lst in e.counter.deltas.values() for d in lst
        ]
        gold_deltas = [
            d for e in edges for d in e.counter.deltas.get("gold", [])
        ]
        flash_p99 = pctile(flash_deltas, 99)
        slo.check("flash.p99", flash_p99, p99_ceiling)
        if gold_deltas:
            slo.check("flash.gold_p99", pctile(gold_deltas, 99), gold_ceiling)
        require(
            sum(e.node.evictions for e in edges) == evictions_before,
            "the flash crowd evicted healthy admitted sessions",
        )
        await until(quiesced, timeout_s, "S2 queue drain (bounded growth)")
        results["flash"] = {
            "attempts": sum(attempts.values()),
            "admitted": sum(admitted.values()),
            "shed": sum(shed.values()),
            "by_lane": {"gold": dict(admitted=admitted["gold"], shed=shed["gold"]),
                        "anon": dict(admitted=admitted["anon"], shed=shed["anon"])},
            "gold_shed_rate": round(gold_rate, 4),
            "anon_shed_rate": round(anon_rate, 4),
            "arrival_s": round(arrival_s, 2),
            "p99_ms": flash_p99,
            "p50_ms": pctile(flash_deltas, 50),
        }

        # ========================================================== S3
        # mass-reconnect storm: park, fence while away, replay the tokens
        # through the RESERVED resume lane
        if not smoke and reconnect_n > 0:
            note(f"S3: mass-reconnect storm ({reconnect_n} resumes)...")
            victims = base_sessions[:reconnect_n]
            tokens = []
            for e, sid, tenant, session in victims:
                tokens.append((e, sid, tenant, e.node.detach(session, park=True)))
            await burst(key_rows, collect=False)  # fences they all MISS
            resume_shed = 0
            t0 = time.perf_counter()
            resumed = []
            for e, sid, tenant, token in tokens:
                try:
                    s2 = e.node.resume(
                        token, sink=e.make_sink(last, sid, tenant), tenant=tenant
                    )
                    resumed.append((e, sid, s2))
                except AdmissionRejected:
                    resume_shed += 1
                if len(resumed) % 256 == 255:
                    await asyncio.sleep(0)
            storm_s = time.perf_counter() - t0
            await settle(0.2)
            # the resume lane is RESERVED: zero sheds, and every resumed
            # session replayed the fence it missed (parked-state serving)
            slo.check_eq("reconnect.resume_lane_shed", resume_shed, 0)
            stale = 0
            for e, sid, s2 in resumed:
                for ks in s2.keys:
                    frame = last.get((e.i, sid, ks))
                    if frame is None or frame[5] is not None:
                        stale += 1
                        continue
                    sub = e.node._subs.get(ks)
                    if sub is None or frame[1] < sub.version:
                        stale += 1
            slo.check_eq("reconnect.stale_after_resume", stale, 0)
            slo.check("reconnect.storm_s", round(storm_s, 2),
                      reconnect_slo_s, unit="s")
            results["reconnect"] = {
                "storm": reconnect_n,
                "resumed": len(resumed),
                "shed": resume_shed,
                "storm_s": round(storm_s, 2),
            }
            await until(quiesced, timeout_s, "S3 queue drain")

        # ========================================================== S4
        # rolling edge restart: drain mid-traffic, successor imports the
        # parked state, ZERO deliveries lost
        note("S4: rolling restart (drain mid-traffic)...")
        victim = edges[-1]
        drained_ids = [
            (sid, tenant, session)
            for e, sid, tenant, session in base_sessions
            if e is victim and not session.evicted
        ]
        stop_bursts = asyncio.Event()

        async def background_bursts():
            while not stop_bursts.is_set():
                await burst(key_rows, collect=False, wait_timeout=5.0)
                await asyncio.sleep(0.05)

        burster = asyncio.create_task(background_bursts())
        await asyncio.sleep(0.1)
        export = await victim.node.drain()
        require(victim.node.draining, "drain flag never latched")
        sessions_drained = victim.node.sessions_drained
        require(
            victim.node.drains == 1 and sessions_drained >= len(drained_ids),
            "drain counters missing",
        )
        # every drained session got its reconnect hint WITH its token
        hints_ok = 0
        for sid, _tenant, session in drained_ids:
            frame = last.get((victim.i, sid, DRAIN_KEY))
            if frame is not None and frame[2].get("resume") == session.token:
                hints_ok += 1
        require(
            hints_ok == len(drained_ids),
            f"{len(drained_ids) - hints_ok} sessions missed their drain hint",
        )
        # admission now sheds with reason=draining (counted)
        try:
            victim.node.attach([hot_spec], sink=lambda f: None)
            require(False, "a draining edge admitted a cold attach")
        except AdmissionRejected as e:
            require(
                e.decision.reason == "draining",
                f"drain shed reason {e.decision.reason}",
            )
        # hand off: close the old node, stand up the successor, import
        await victim.node.close()
        successor = AdmissionController(
            registry=registry,
            connect_rate=knobs["connect_rate"],
            connect_burst=knobs["connect_burst"],
            resume_rate=knobs["resume_rate"],
            resume_burst=knobs["resume_burst"],
            max_concurrent=knobs["max_concurrent"],
            name=f"edge-{victim.i}b",
        )
        new_node = EdgeNode(
            "dag", victim.rpc, router=victim.router, name=f"edge-{victim.i}b",
            fan_workers=2, reread_batch=True, value_blocks=False,
            admission=successor, resume_ttl=120.0,
        )
        adopted = new_node.import_parked(export)
        require(
            adopted >= len(drained_ids),
            f"successor adopted {adopted} of {len(drained_ids)} parked tokens",
        )
        victim.node = new_node
        victim.admission = successor
        # resume every drained session on the successor (resume lane)
        for sid, tenant, session in drained_ids:
            new_node.resume(
                session.token, sink=victim.make_sink(last, sid, tenant),
                tenant=tenant,
            )
        await asyncio.sleep(0.2)
        stop_bursts.set()
        await burster
        # final generation, then the ZERO-LOSS audit: every (session, key)
        # converged to the oracle despite the fences during the gap
        await burst(key_rows, collect=False)
        await settle(0.2)
        drain_loss = 0
        for sid, _tenant, session in drained_ids:
            for ks in session.keys:
                frame = last.get((victim.i, sid, ks))
                row = None
                sub = new_node._subs.get(ks)
                if sub is not None:
                    row = sub.args[0]
                if (
                    frame is None
                    or frame[5] is not None
                    or row is None
                    or float(frame[2]) != oracle(row)
                ):
                    drain_loss += 1
        slo.check_eq("drain.deliveries_lost", drain_loss, 0)
        results["drain"] = {
            "sessions_drained": sessions_drained,
            "audited_sessions": len(drained_ids),
            "hints": hints_ok,
            "adopted": adopted,
            "drain_loss": drain_loss,
        }
        await until(quiesced, timeout_s, "S4 queue drain")

        # ========================================================== S5
        # reshard mid-flash-crowd: the shard map moves ~half the keys to
        # s1 WHILE a second crowd arrives on a hot key
        if not smoke:
            note("S5: reshard mid-flash-crowd...")
            crowd2 = max(200, flash_n // 4)
            new_map = edges[0].router.shard_map.with_members(["s0", "s1"])
            moved = len(ShardMap.diff(edges[0].router.shard_map, new_map))
            require(moved > 0, "the reshard moved nothing")
            hot2 = key_specs[1]
            admitted2 = shed2 = 0
            for j in range(crowd2):
                e = edges[j % n_edges]
                if j == crowd2 // 2:
                    for e2 in edges:
                        e2.node.apply_map(new_map)  # MID-crowd
                try:
                    e.node.attach(
                        [hot2], sink=e.make_sink(last, f"r{j}", ""),
                        track_versions=False, replay_current=False,
                    )
                    admitted2 += 1
                except AdmissionRejected:
                    shed2 += 1
                if j % 256 == 255:
                    await asyncio.sleep(0)
            await until(
                lambda: sum(e.node.resubscribes for e in edges) > 0,
                timeout_s, "post-reshard re-pins",
            )
            for e in edges:
                require(
                    len(e.node._subs) == n_keys,
                    f"edge {e.i} upstream subs {len(e.node._subs)} != {n_keys} "
                    f"after reshard (single-upstream invariant broke)",
                )
            # let the repins settle (moved keys re-capture at s1), then a
            # full generation must converge oracle-clean
            await settle(0.5)
            await burst(key_rows)
            reshard_p99 = pctile(
                [d for e in edges for lst in e.counter.deltas.values() for d in lst],
                99,
            )
            slo.check("reshard.p99", reshard_p99, p99_ceiling)
            results["reshard"] = {
                "moved_shards": moved,
                "crowd": crowd2,
                "admitted": admitted2,
                "shed": shed2,
                "resubscribes": sum(e.node.resubscribes for e in edges),
                "p99_ms": reshard_p99,
            }
            await until(quiesced, timeout_s, "S5 queue drain")

        # ========================================================== S6
        # write-path burst (ISSUE 20): commands → fused waves → fences.
        # The command plane rides THIS stack: orders route through the
        # ClusterCommander, completion's invalidation replay is collected
        # and submitted through the nonblocking pipeline, and the
        # subscribed sessions see the fences.
        note("S6: write-path burst (commands fuse into waves)...")
        from stl_fusion_tpu.commands import ClusterCommander
        from stl_fusion_tpu.core import is_invalidating
        from stl_fusion_tpu.diagnostics import global_metrics as _gm

        orders: dict = {}

        async def apply_order(command):
            if is_invalidating():
                await svc.node(command.row)
                return
            orders[command.row] = orders.get(command.row, 0) + command.qty
            return float(orders[command.row])

        hub.commander.add_handler(apply_order, command_type=OrderCmd)
        hub.commander.attach_operations_pipeline()
        pipe = hub.enable_nonblocking(fuse_depth=8)
        cc = ClusterCommander(hub.commander, member_id="s0")
        write_rows = key_rows[: min(8, n_keys)]
        write_rounds = 2 if smoke else 4
        eager_before = pipe.stats()["eager_waves"]
        vis_hist = _gm().histogram(
            "fusion_cmd_visible_ms",
            help="command acceptance → client-visible invalidation",
            unit="ms",
        )
        hist_ck = vis_hist.checkpoint()
        round_ms = []
        fenced_write_keys = {
            edges[0].node.key_str(spec_of_row[r]) for r in write_rows
        }
        for rnd in range(write_rounds):
            for e in edges:
                expected = sum(
                    sub.session_count
                    for ks, sub in e.node._subs.items()
                    if ks in fenced_write_keys
                )
                e.counter.arm(expected, collect=False)
            t0 = time.perf_counter()
            for j, row in enumerate(write_rows):
                await cc.call(OrderCmd(int(row), 1),
                              operation_id=f"op-traffic-{rnd}-{j}")
            cc.drain()  # flush + harvest: the commands' super-round lands
            await asyncio.wait_for(
                asyncio.gather(*(e.counter.event.wait() for e in edges)),
                timeout_s,
            )
            round_ms.append((time.perf_counter() - t0) * 1e3)
        # the duplicate operation id is ABSORBED, never re-applied
        dedup_before = _gm().counter("fusion_cmd_dedup_total").value
        before_dup = orders[int(write_rows[0])]
        again = await cc.call(OrderCmd(int(write_rows[0]), 1),
                              operation_id="op-traffic-0-0")
        require(
            orders[int(write_rows[0])] == before_dup and again == 1.0,
            "duplicate order op id re-applied (memo must return the FIRST "
            "application's result and leave the ledger untouched)",
        )
        require(
            _gm().counter("fusion_cmd_dedup_total").value == dedup_before + 1,
            "dedup replay not counted",
        )
        write_p99 = pctile(round_ms, 99)
        slo.check("write.cmd_visible_p99", write_p99, p99_ceiling)
        slo.check_eq(
            "write.eager_waves",
            int(pipe.stats()["eager_waves"] - eager_before), 0,
        )
        require(
            vis_hist.since(hist_ck)["count"] >= write_rounds * len(write_rows),
            "fusion_cmd_visible_ms never recorded the command waves",
        )
        require(
            sum(orders.values()) == write_rounds * len(write_rows),
            "order ledger lost or double-applied a write",
        )
        results["write"] = {
            "rounds": write_rounds,
            "orders": sum(orders.values()),
            "cmd_visible_p99_ms": write_p99,
            "eager_waves": int(pipe.stats()["eager_waves"] - eager_before),
            "fused_dispatches": pipe.stats()["fused_dispatches"],
        }
        pipe.dispose()  # back to the blocking burst path for the audits
        await until(quiesced, timeout_s, "S6 queue drain")

        # ================================================== final audits
        note("final staleness + consistency audit...")
        await burst(key_rows, collect=False)
        await settle(0.2)
        stale_final = 0
        audited = 0
        for e in edges:
            for ks, sub in e.node._subs.items():
                if sub.session_count == 0 or sub.last_frame is None:
                    continue
                audited += 1
                if (
                    sub.last_frame[5] is not None
                    or float(sub.last_frame[2]) != oracle(sub.args[0])
                ):
                    stale_final += 1
        require(audited > 0, "staleness audit audited nothing")
        slo.check_eq("audit.stale_keys", stale_final, 0)
        auditor = ConsistencyAuditor(hub, backend=backend, period=3600.0)
        audit_report = await auditor.audit_once()
        n_violations = len(audit_report.get("violations", []))
        slo.check_eq("audit.invariant_violations", n_violations, 0)
        results["audit"] = {
            "keys_audited": audited,
            "stale": stale_final,
            "violations": n_violations,
            "canary_staleness_ms": audit_report.get("canary_staleness_ms"),
        }

        # counted-never-silent: the drain and every shed show in metrics
        from stl_fusion_tpu.diagnostics import global_metrics

        exposition = global_metrics().render_prometheus()
        require(
            "fusion_edge_drains_total" in exposition,
            "fusion_edge_drains_total missing from the exposition",
        )
        require(
            'fusion_edge_shed_total{reason="rate"}' in exposition,
            "per-reason shed counters missing from the exposition",
        )
        require(
            'fusion_edge_admitted_total{lane="anonymous"}' in exposition,
            "per-lane admitted counters missing from the exposition",
        )

        results["admission"] = {
            "per_edge": [e.admission.snapshot() for e in edges],
        }
        results["generations"] = gen["v"]
        slo.enforce()
        results["slo"] = slo.checks
        results["ok"] = True
        print(json.dumps(results))
        note("done")
        for e in edges:
            await e.node.close()
            await e.rpc.stop()
        for rpc in servers.values():
            await rpc.stop()
    finally:
        set_default_hub(old)


if __name__ == "__main__":
    asyncio.run(main())
