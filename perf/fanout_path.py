#!/usr/bin/env python
"""Fan-out benchmark: the 10 M-node live burst meeting the RPC layer.

VERDICT r5 missing #4: the server-pushes-$sys-c-to-every-subscribed-client
behavior — the reference's defining distributed mechanism — was implemented
and chaos-tested but never MEASURED; no number existed for clients fenced
per second or the client-observed staleness window, and the 10 M burst and
the RPC layer had never run together. This benchmark runs both at once:

- **server**: the live-path stack (FusionHub + TpuGraphBackend + a
  table-backed DAG service, columnar bulk ingest, topo mirror) driving
  lane-packed bursts (``cascade_rows_lanes``) over FANOUT_NODES rows;
- **clients**: FANOUT_CLIENTS in-process fusion clients, each on its own
  RpcHub over a twisted in-memory channel pair (rpc/testing.py — the same
  transport the protocol tests trust), each holding FANOUT_KEYS live
  ``$sys-c`` subscriptions (one per compute call) across the table;
- **measurement**: per round, every subscription's ``when_invalidated``
  future is armed BEFORE the burst; the burst fires; the recorded numbers
  are when each client OBSERVED its invalidation. Reported per mode:
  ``clients_fenced_per_s`` (deliveries / post-burst fan-out seconds),
  ``keys_per_frame``, ``coalesce_ratio`` (per-key frames each batch frame
  replaced), ``staleness_ms_p50/p99`` (burst dispatch → client observed,
  burst device time included) and ``delivery_ms_p50/p99`` (wave applied →
  client observed — the pure fan-out window).

Modes (the A/B the coalescer must win):
- ``perkey``  — the original wire shape: one awaited ``$sys-c.invalidate``
  frame per subscription per peer (hub.coalesce_invalidations=False, no
  fanout index);
- ``coalesced`` — the ISSUE-2 tentpole: the burst's newly-mask drains
  subscribed keys through the ComputeFanoutIndex into per-peer outbox
  pending sets, one ``$sys-c.invalidate_batch`` frame per drain tick.

Also measured: single-client single-key lone invalidation latency in both
modes (the no-regression guard for the non-burst path).

Env: FANOUT_NODES (default 10_000_000), FANOUT_CLIENTS (100), FANOUT_KEYS
(16 per client), FANOUT_ROUNDS (2), FANOUT_GROUPS (32 lane groups),
FANOUT_SEEDS_PER_GROUP (4 deep seeds added per group — the burst's 10 M
closure), FANOUT_DEG (3), FANOUT_MODES (both|coalesced|perkey),
FANOUT_LONE_SAMPLES (24; 0 skips).

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


from stl_fusion_tpu.client import compute_client, install_compute_call_type  # noqa: E402
from stl_fusion_tpu.core import (  # noqa: E402
    ComputeService,
    FusionHub,
    TableBacking,
    capture,
    compute_method,
    memo_table_of,
    set_default_hub,
)
from stl_fusion_tpu.graph import TpuGraphBackend  # noqa: E402
from stl_fusion_tpu.graph.synthetic import power_law_dag  # noqa: E402
from stl_fusion_tpu.rpc import RpcHub, RpcTestTransport, install_compute_fanout  # noqa: E402


def make_dag_service(n: int):
    class DagTable(ComputeService):
        """The benchmark DAG as a table-backed service (live_path's shape):
        row values derive from a base array; dependency topology declared
        in bulk; device loader serves warms/refreshes."""

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
    """Counts client-observed invalidations with SYNC callbacks — no
    per-subscription future/gather machinery inflating the floor both
    modes share (the callback runs inside the node's invalidation, i.e.
    at the moment a client reader would see staleness)."""

    def __init__(self):
        self.times: list = []
        self.remaining = 0
        self.event = asyncio.Event()

    def arm(self, count: int) -> None:
        self.times = []
        self.remaining = count
        self.event.clear()

    def hit(self, _c=None) -> None:
        self.times.append(time.perf_counter())
        self.remaining -= 1
        if self.remaining <= 0:
            self.event.set()


class Client:
    """One in-process fusion client: own FusionHub + RpcHub + transport
    (codec-faithful by default — every frame pays envelope serialization
    both ways, like a socket link)."""

    def __init__(self, i: int, server_rpc: RpcHub, wire_codec: bool):
        self.i = i
        self.fusion = FusionHub()
        self.rpc = RpcHub(f"client-{i}")
        install_compute_call_type(self.rpc)
        self.transport = RpcTestTransport(self.rpc, server_rpc, wire_codec=wire_codec)
        # unique peer ref → unique server-side peer ("client:c{i}")
        self.proxy = compute_client("dag", self.rpc, self.fusion, peer_ref=f"c{i}")
        self.keys: np.ndarray = np.empty(0, dtype=np.int64)
        self.nodes: dict = {}

    async def subscribe(self, observer: Observer) -> None:
        """(Re-)read every key; each node reports its invalidation to the
        shared observer the moment the client applies it."""
        for k in self.keys.tolist():
            node = await capture(lambda k=k: self.proxy.node(int(k)))
            self.nodes[k] = node
            node.on_invalidated(observer.hit)


async def settle(seconds: float = 0.05) -> None:
    """Let queued tasks (watch registrations, outbox drains) run."""
    deadline = time.perf_counter() + seconds
    while time.perf_counter() < deadline:
        await asyncio.sleep(0.005)


def percentiles(samples_ms):
    arr = np.asarray(samples_ms)
    if arr.size == 0:
        return None, None
    return (
        round(float(np.percentile(arr, 50)), 3),
        round(float(np.percentile(arr, 99)), 3),
    )


async def run_mode(
    mode, backend, block, server_rpc, clients, groups, rounds, timeout_s, fanout_index
):
    """Drive ``rounds`` subscribe→burst→observe cycles; returns the mode's
    metric dict. ``mode`` flips the hub flag (and the index stays inert in
    perkey mode because nothing registers while compute_fanout is None)."""
    coalesced = mode == "coalesced"
    server_rpc.coalesce_invalidations = coalesced
    server_rpc.compute_fanout = fanout_index if coalesced else None
    # counter snapshot (outboxes accumulate across modes)
    snap = server_rpc.fanout_stats()
    # per-mode slice of the SYSTEM's delivery histogram: the global
    # histogram accumulates across modes and the lone-latency probes, so a
    # whole-run snapshot would blend per-key and coalesced samples — the
    # checkpoint diff isolates exactly this mode's distribution
    from stl_fusion_tpu.diagnostics import global_metrics

    delivery_hist = global_metrics().histogram(
        "fusion_e2e_delivery_ms",
        help="server wave apply -> client invalidation apply",
    )
    delivery_cp = delivery_hist.checkpoint()

    total_subs = sum(len(c.keys) for c in clients)
    observer = Observer()
    fanout_s = 0.0
    burst_dev_s = 0.0
    churn_flush_s = 0.0
    staleness_ms = []
    delivery_ms = []
    total_inv = 0
    for rnd in range(rounds):
        observer.arm(total_subs)
        t0 = time.perf_counter()
        # clients subscribe CONCURRENTLY (each client's keys in order):
        # per-subscription cost is dominated by dispatch latency, which
        # overlaps across clients
        await asyncio.gather(*(c.subscribe(observer) for c in clients))
        sub_s = time.perf_counter() - t0
        await settle()
        # absorb the re-subscription churn OUTSIDE the timed burst: each
        # recompute journaled an epoch bump + in-edge redeclare, and their
        # per-op device journal apply is the live pipeline's known scalar-
        # churn cost (live_path itemizes it the same way) — not fan-out
        t0 = time.perf_counter()
        backend.flush()
        churn_flush_s += time.perf_counter() - t0
        t0 = time.perf_counter()
        counts = backend.cascade_rows_lanes(block, groups)
        t_burst = time.perf_counter()
        await asyncio.wait_for(observer.event.wait(), timeout_s)
        t_all = time.perf_counter()
        observed = observer.times
        total_inv += int(counts.sum())
        burst_dev_s += t_burst - t0
        fanout_s += t_all - t_burst
        staleness_ms.extend((t - t0) * 1e3 for t in observed)
        delivery_ms.extend((t - t_burst) * 1e3 for t in observed)
        note(
            f"[{mode}] round {rnd}: burst {t_burst - t0:.2f}s "
            f"({int(counts.sum()):,} inv), fan-out {t_all - t_burst:.3f}s "
            f"({total_subs} subs), subscribe {sub_s:.2f}s, "
            f"churn flush {churn_flush_s:.2f}s cumulative"
        )
        # restore consistency for the next round (device refresh — the
        # live churn-recompute path; scalar twins recompute on next read)
        backend.refresh_block_on_device(block)
        backend.flush()
        await settle()
    stats = server_rpc.fanout_stats()
    delta = {
        k: stats[k] - snap.get(k, 0)
        for k in (
            "invalidations_posted", "invalidations_coalesced",
            "batch_frames_sent", "batch_keys_sent", "messages_sent",
        )
    }
    st_p50, st_p99 = percentiles(staleness_ms)
    dv_p50, dv_p99 = percentiles(delivery_ms)
    fenced = total_subs * rounds
    frames = delta["batch_frames_sent"]
    return {
        # the system's own delivery numbers for THIS mode (ISSUE 3): must
        # agree with the harness-measured delivery_ms_p50/p99 below to
        # bucket resolution — the in-system histogram owns the number now
        "system_delivery_ms": delivery_hist.since(delivery_cp),
        "clients_fenced_total": fenced,
        "clients_fenced_per_s": round(fenced / fanout_s, 1) if fanout_s else None,
        "fanout_s": round(fanout_s, 4),
        "burst_s": round(burst_dev_s, 3),
        "churn_flush_s": round(churn_flush_s, 3),
        "burst_inv_total": total_inv,
        "staleness_ms_p50": st_p50,
        "staleness_ms_p99": st_p99,
        "delivery_ms_p50": dv_p50,
        "delivery_ms_p99": dv_p99,
        "batch_frames": frames,
        "keys_per_frame": (
            round(delta["batch_keys_sent"] / frames, 1) if frames else None
        ),
        # per-key frames each batch frame replaced (posted counts dups that
        # the pending map deduped)
        "coalesce_ratio": (
            round(delta["invalidations_posted"] / frames, 1) if frames else None
        ),
        "invalidations_posted": delta["invalidations_posted"],
    }


async def run_lone_ab(backend, block, server_rpc, client, samples, fanout_index):
    """Single-client single-key invalidation latency A/B (the non-burst
    path must not regress under coalescing). Modes ALTERNATE per sample so
    both see the same accumulated graph state — a per-mode block would
    charge whichever runs later for the churn the earlier one left."""
    key = int(client.keys[0])
    lat_ms = {"coalesced": [], "perkey": []}
    observer = Observer()
    for i in range(samples * 2):
        mode = ("coalesced", "perkey")[i % 2]
        server_rpc.coalesce_invalidations = mode == "coalesced"
        server_rpc.compute_fanout = fanout_index if mode == "coalesced" else None
        node = await capture(lambda: client.proxy.node(key))
        observer.arm(1)
        node.on_invalidated(observer.hit)
        await settle(0.01)
        backend.flush()  # absorb the re-subscription's recompute journal
        t0 = time.perf_counter()
        backend.cascade_rows_batch(block, [key])
        await asyncio.wait_for(observer.event.wait(), 30.0)
        lat_ms[mode].append((time.perf_counter() - t0) * 1e3)
        backend.refresh_block_on_device(block)
        backend.flush()
        await settle(0.005)
    out = {}
    for mode, arr in lat_ms.items():
        p50, p99 = percentiles(arr)
        out[f"{mode}_lone_ms_p50"] = p50
        out[f"{mode}_lone_ms_p99"] = p99
    out["lone_samples_per_mode"] = samples
    return out


async def main() -> None:
    from stl_fusion_tpu.graph import enable_program_cache, require_accelerator

    device = require_accelerator("perf/fanout_path.py")
    enable_program_cache()
    n = int(os.environ.get("FANOUT_NODES", 10_000_000))
    n_clients = int(os.environ.get("FANOUT_CLIENTS", 100))
    # (re-subscription storms are affordable now that flush() coalesces
    # the bump/epack journal pairs — 1600 recomputes replay as 2 device
    # dispatches, not 3200; pre-fix this forced keys down to 8)
    keys_per_client = int(os.environ.get("FANOUT_KEYS", 16))
    rounds = int(os.environ.get("FANOUT_ROUNDS", 2))
    n_groups = int(os.environ.get("FANOUT_GROUPS", 32))
    seeds_per_group = int(os.environ.get("FANOUT_SEEDS_PER_GROUP", 4))
    deg = float(os.environ.get("FANOUT_DEG", 3))
    modes = os.environ.get("FANOUT_MODES", "both")
    lone_samples = int(os.environ.get("FANOUT_LONE_SAMPLES", 24))
    timeout_s = float(os.environ.get("FANOUT_TIMEOUT_S", 600))
    wire_codec = os.environ.get("FANOUT_WIRE", "1") == "1"
    rng = np.random.default_rng(97)

    note(f"generating {n}-node power-law DAG...")
    src, dst = power_law_dag(n, avg_degree=deg, seed=7)

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(
            hub, node_capacity=n + 64,
            # headroom: every round's scalar recomputes re-declare their
            # rows' in-edges at the new epoch
            edge_capacity=len(src) + max(65536, 8 * n_clients * keys_per_client * rounds),
        )
        Dag = make_dag_service(n)
        svc = Dag(hub)
        hub.add_service(svc, "dag")
        table = memo_table_of(svc.node)

        note(f"columnar build of the {n}-node live graph...")
        t0 = time.perf_counter()
        block = backend.bind_table_rows(table)
        backend.declare_row_edges(block, src, block, dst)
        backend.warm_block_on_device(block)
        backend.flush()
        build_s = time.perf_counter() - t0
        note(f"built in {build_s:.1f}s; building topo mirror...")
        t0 = time.perf_counter()
        backend.graph.build_topo_mirror()
        mirror_s = time.perf_counter() - t0
        note(f"mirror in {mirror_s:.1f}s")

        server_rpc = RpcHub("server")
        install_compute_call_type(server_rpc)
        server_rpc.add_service("dag", svc)
        fanout_index = install_compute_fanout(server_rpc, backend)

        # subscribed keys: tail rows (shallow closures — the subscription
        # cost is what's under test, not each key's own cascade); the burst
        # adds deep seeds so the wave still walks the 10M graph
        all_keys = (
            n - 1 - rng.choice(n // 4, size=n_clients * keys_per_client, replace=False)
        )
        clients = []
        for i in range(n_clients):
            c = Client(i, server_rpc, wire_codec)
            c.keys = np.sort(all_keys[i * keys_per_client : (i + 1) * keys_per_client])
            clients.append(c)

        # burst groups: subscribed keys round-robined across groups, plus
        # deep random seeds per group for the full-scale closure
        groups = [list() for _ in range(n_groups)]
        for j, k in enumerate(all_keys.tolist()):
            groups[j % n_groups].append(int(k))
        deep = rng.choice(n // 10, size=(n_groups, seeds_per_group), replace=False)
        for gi in range(n_groups):
            groups[gi].extend(int(s) for s in deep[gi])

        note("warming lane + refresh programs (untimed)...")
        t0 = time.perf_counter()
        backend.cascade_rows_lanes(block, groups)
        backend.refresh_block_on_device(block)
        backend.cascade_rows_batch(block, [n - 1])
        backend.refresh_block_on_device(block)
        backend.flush()
        warm_s = time.perf_counter() - t0
        note(f"programs warm ({warm_s:.1f}s); connecting {n_clients} clients...")

        mode_list = ["perkey", "coalesced"] if modes == "both" else [modes]
        results = {}
        for mode in mode_list:
            results[mode] = await run_mode(
                mode, backend, block, server_rpc, clients, groups, rounds,
                timeout_s, fanout_index,
            )
        lone = {}
        if lone_samples > 0:
            lone = await run_lone_ab(
                backend, block, server_rpc, clients[0], lone_samples, fanout_index
            )
        speedup = None
        if "perkey" in results and "coalesced" in results:
            a = results["coalesced"]["clients_fenced_per_s"]
            b = results["perkey"]["clients_fenced_per_s"]
            if a and b:
                speedup = round(a / b, 2)
        result = {
            "metric": "fanout_path",
            **device,
            "nodes": n,
            "edges": int(backend.edge_count),
            "clients": n_clients,
            "keys_per_client": keys_per_client,
            "subscriptions": n_clients * keys_per_client,
            "rounds": rounds,
            "lane_groups": n_groups,
            "wire_codec": wire_codec,
            "build_s": round(build_s, 2),
            "mirror_build_s": round(mirror_s, 2),
            "coalesced_vs_perkey_speedup": speedup,
            **{f"{m}_{k}": v for m, r in results.items() for k, v in r.items()},
            **lone,
        }
        print(json.dumps(result))
        note("done")
        for c in clients:
            await c.rpc.stop()
        await server_rpc.stop()
    finally:
        set_default_hub(old)


if __name__ == "__main__":
    asyncio.run(main())
