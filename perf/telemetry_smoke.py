#!/usr/bin/env python
"""Telemetry smoke (ISSUE 3 + 4 CI step): boot a small server+client pair,
drive one burst through the full stack (device wave → fanout index → outbox
batch frame → wire-codec channel → client apply), then scrape the HTTP
gateway's ``/metrics`` and assert

- the Prometheus exposition PARSES (every sample line is ``name value``),
- the end-to-end delivery histogram (``fusion_e2e_delivery_ms``) is
  NON-EMPTY — i.e. the system measured its own fan-out latency, no harness
  stopwatch involved,
- ``/trace`` serves JSON with the monitor report (waves + delivery +
  recorder), and ``?section=`` bounds the payload to one section,
- ``/explain?key=`` assembles a causal chain that NAMES the burst wave's
  cause id (the ISSUE 4 acceptance: the "why" answer works over HTTP),
- the NONBLOCKING fused path actually ENGAGES (ISSUE 7 CI gate): after
  driving the wave pipeline, ``fusion_wave_fused_depth`` is non-empty with
  p50 > 1, ``/trace?section=waves`` shows fused entries
  (``fused_depth`` > 1), and zero waves fell back to eager dispatch — a
  silent regression to one-wave-per-dispatch fails the build.

Prints ONE JSON summary line on stdout; exits non-zero on any failed check.

Env: TELEMETRY_NODES (default 512), TELEMETRY_CLIENTS (4),
TELEMETRY_KEYS (4 per client).
"""
import asyncio
import json
import os
import sys
import urllib.parse

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

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
from stl_fusion_tpu.diagnostics import FusionMonitor, global_metrics  # noqa: E402
from stl_fusion_tpu.graph import TpuGraphBackend  # noqa: E402
from stl_fusion_tpu.rpc import RpcHub, RpcTestTransport, install_compute_fanout  # noqa: E402
from stl_fusion_tpu.rpc.http_gateway import FusionHttpServer  # noqa: E402


def note(msg: str) -> None:
    print(f"# {msg}", file=sys.stderr, flush=True)


async def http_get(host, port, path):
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(
        f"GET {path} HTTP/1.1\r\nHost: {host}\r\nConnection: close\r\n\r\n".encode()
    )
    await writer.drain()
    raw = await reader.read()
    writer.close()
    head, _, body = raw.partition(b"\r\n\r\n")
    return head.split(b"\r\n", 1)[0].decode(), body


def parse_exposition(text: str) -> dict:
    """Every non-comment line must be ``name value`` with a float value —
    the 'exposition parses' acceptance check."""
    samples = {}
    for line in text.strip().splitlines():
        if not line or line.startswith("#"):
            continue
        name, value = line.rsplit(" ", 1)
        samples[name] = float(value)
    return samples


async def main() -> int:
    n = int(os.environ.get("TELEMETRY_NODES", 512))
    n_clients = int(os.environ.get("TELEMETRY_CLIENTS", 4))
    keys_per_client = int(os.environ.get("TELEMETRY_KEYS", 4))

    # SLO burn windows compressed to smoke scale (ISSUE 19): the health
    # leg must see ok -> burning -> warn -> ok inside seconds, not the
    # production minutes. Must land BEFORE the first /health evaluation
    # mints the global SloEngine (windows are read at construction).
    os.environ.setdefault("FUSION_SLO_FAST_S", "0.8")
    os.environ.setdefault("FUSION_SLO_SLOW_S", "3.2")
    os.environ.setdefault("FUSION_SLO_HOLD_S", "0.6")
    # CPU CI boxes are not latency SLO subjects — park the p99 budget out
    # of the way so this leg exercises the shed SLO, not scheduler noise
    os.environ.setdefault("FUSION_SLO_DELIVERY_P99_MS", "60000")

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub, node_capacity=n + 8, edge_capacity=4 * n)

        class Tbl(ComputeService):
            def __init__(self, h=None):
                super().__init__(h)
                self.base = np.arange(n, dtype=np.float32)

            def load(self, ids):
                return self.base[np.asarray(ids, dtype=np.int64)]

            @compute_method(table=TableBacking(rows=n, batch="load"))
            async def node(self, i: int) -> float:
                return float(self.base[i])

        svc = Tbl(hub)
        hub.add_service(svc, "tbl")
        table = memo_table_of(svc.node)
        block = backend.bind_table_rows(table)
        src = np.arange(0, n - 1, dtype=np.int64)
        dst = np.arange(1, n, dtype=np.int64)  # one long chain
        backend.declare_row_edges(block, src, block, dst)
        table.read_batch(np.arange(n))
        backend.flush()

        server_rpc = RpcHub("server")
        install_compute_call_type(server_rpc)
        server_rpc.add_service("tbl", svc)
        install_compute_fanout(server_rpc, backend)
        monitor = FusionMonitor(hub).attach_rpc_hub(server_rpc)
        monitor.start_reporter(period=30.0)

        gateway = FusionHttpServer(server_rpc)
        gateway.monitor = monitor
        await gateway.start()
        note(f"gateway at {gateway.url}")

        # clients subscribe over codec-faithful channels
        nodes = []
        client_rpcs = []
        for i in range(n_clients):
            crpc = RpcHub(f"client-{i}")
            install_compute_call_type(crpc)
            RpcTestTransport(crpc, server_rpc, wire_codec=True)
            proxy = compute_client("tbl", crpc, FusionHub(), peer_ref=f"c{i}")
            for k in range(keys_per_client):
                key = n - 1 - (i * keys_per_client + k)
                nodes.append(await capture(lambda key=key: proxy.node(int(key))))
            client_rpcs.append(crpc)
        note(f"{len(nodes)} subscriptions live; bursting from row 0...")

        backend.cascade_rows_batch(block, [0])  # the chain fences every key
        await asyncio.wait_for(
            asyncio.gather(*(nd.when_invalidated() for nd in nodes)), 30.0
        )
        await asyncio.sleep(0.05)  # let outbox drains settle

        status, body = await http_get(gateway.host, gateway.port, "/metrics")
        assert status.endswith("200 OK"), status
        samples = parse_exposition(body.decode())
        delivery_count = samples.get("fusion_e2e_delivery_ms_count", 0)
        assert delivery_count >= len(nodes), (
            f"e2e delivery histogram has {delivery_count} samples, "
            f"expected >= {len(nodes)} — the system did not measure its own fan-out"
        )
        assert samples.get("fusion_batch_frames_sent_total", 0) >= 1
        assert samples.get("fusion_waves_run_total", 0) >= 1

        status, body = await http_get(gateway.host, gateway.port, "/trace")
        assert status.endswith("200 OK"), status
        trace = json.loads(body)
        report = trace["report"]
        assert report["delivery"]["count"] >= len(nodes)
        assert report["waves"]["waves_recorded"] >= 1
        assert report["recorder"]["events_recorded"] >= 1
        cause = report["waves"]["recent"][-1]["cause"]
        assert nodes[0].invalidation_cause == cause, (
            nodes[0].invalidation_cause, cause,
        )

        # section bound: a scraper can fetch ONE report section
        status, body = await http_get(gateway.host, gateway.port, "/trace?section=waves")
        assert status.endswith("200 OK"), status
        sec = json.loads(body)
        assert set(sec) == {"report"} and set(sec["report"]) == {"waves"}

        # /explain?key=: the causal chain names the burst wave's cause id
        # (ISSUE 4 acceptance, over plain HTTP)
        from stl_fusion_tpu.diagnostics import RECORDER

        # the SERVER-side key of the fenced tail row (clients share this
        # process's recorder, so a bare fragment match could land on the
        # client-side key — fence events are journaled server-side)
        keys = [
            e["key"]
            for e in RECORDER.recent(kind="client_fenced")
            if f".node({n - 1},)" in (e["key"] or "")
        ]
        assert keys, "flight recorder holds no fence event for the tail row"
        status, body = await http_get(
            gateway.host, gateway.port, "/explain?key=" + urllib.parse.quote(keys[-1])
        )
        assert status.endswith("200 OK"), status
        explain_payload = json.loads(body)
        assert explain_payload["invalidation"]["cause"] == cause, (
            explain_payload["invalidation"], cause,
        )
        assert any(cause in line for line in explain_payload["chain"]), (
            explain_payload["chain"]
        )
        assert explain_payload["invalidation"]["clients_fenced"] >= 1

        # -------- nonblocking fused chain (ISSUE 7 CI gate): drive the
        # wave pipeline and assert the fused path ENGAGED — the histogram,
        # the /trace entries, and the zero-eager-fallback check together
        # make a silent regression to eager dispatch a red build
        stale = np.nonzero(table._stale_host)[0]
        if stale.size:
            table.read_batch(stale)
        backend.flush()
        pipe = hub.enable_nonblocking(fuse_depth=4)
        for k in range(4):
            pipe.submit_rows(block, [k])
        pipe.drain()
        assert pipe.stats()["eager_waves"] == 0, (
            "pipeline fell back to eager dispatch", pipe.stats(),
        )
        status, body = await http_get(
            gateway.host, gateway.port, "/trace?section=waves"
        )
        assert status.endswith("200 OK"), status
        waves_sec = json.loads(body)["report"]["waves"]
        fused_recent = [
            r for r in waves_sec["recent"] if r.get("fused_depth", 1) > 1
        ]
        assert fused_recent, (
            "no fused chain entries in /trace?section=waves",
            waves_sec["recent"][-4:],
        )
        fused_p50 = waves_sec.get("fused_depth_p50")
        assert fused_p50 is not None and fused_p50 > 1, (
            "fusion_wave_fused_depth p50 must exceed 1 (fused path engaged)",
            fused_p50,
        )
        status, body = await http_get(gateway.host, gateway.port, "/metrics")
        assert status.endswith("200 OK"), status
        samples = parse_exposition(body.decode())
        assert samples.get("fusion_wave_fused_depth_count", 0) >= 1, (
            "fused-depth histogram missing from /metrics"
        )
        note(
            f"fused path engaged: depth p50 {fused_p50}, "
            f"{len(fused_recent)} fused /trace entries, 0 eager fallbacks"
        )
        pipe.dispose()

        # -------- health-plane leg (ISSUE 19 CI gate): /health answers a
        # machine-readable verdict; an induced anonymous-lane shed storm
        # must flip the edge_shed_rate SLO to BURNING with the shedding
        # tenant named in the attribution block, and clearing the storm
        # must walk it back through warn (hysteresis) to ok — the full
        # burn-rate arc over plain HTTP, in seconds
        from stl_fusion_tpu.edge.admission import AdmissionController

        status, body = await http_get(gateway.host, gateway.port, "/health")
        assert status.endswith("200 OK"), status
        health = json.loads(body)
        assert health["verdict"] == "ok", health
        assert health["scope"] == "local", health
        slo_names = {s["name"] for s in health["slos"]}
        assert {"delivery_e2e_p99", "superround_eager_rounds",
                "invariant_violations", "edge_shed_rate"} <= slo_names, slo_names

        adm = AdmissionController(shed_pressure=0.5, name="smoke-edge")
        adm.set_pressure("smoke_storm", 1.0)
        states_seen = []
        burning_health = None
        deadline = asyncio.get_event_loop().time() + 20.0
        while asyncio.get_event_loop().time() < deadline:
            for _ in range(64):  # the storm: anonymous cold attaches shed
                adm.admit()
            status, body = await http_get(gateway.host, gateway.port, "/health")
            assert status.endswith("200 OK"), status
            health = json.loads(body)
            shed_slo = next(
                s for s in health["slos"] if s["name"] == "edge_shed_rate"
            )
            states_seen.append(shed_slo["state"])
            if shed_slo["state"] == "burning":
                burning_health = health
                break
            await asyncio.sleep(0.12)
        assert burning_health is not None, (
            "shed storm never drove edge_shed_rate to burning", states_seen,
        )
        assert burning_health["verdict"] == "burning"
        assert burning_health["triggered_by"] == "edge_shed_rate"
        burn_slo = next(
            s for s in burning_health["slos"] if s["name"] == "edge_shed_rate"
        )
        assert burn_slo["burn"]["fast"]["samples"] >= 2, burn_slo["burn"]
        attr = burn_slo.get("attribution")
        assert attr and attr["domain"] == "tenant_sheds", burn_slo
        assert any(e["key"] == "(default)" for e in attr["top"]), attr
        note(
            f"shed storm: edge_shed_rate burning after {len(states_seen)} "
            f"polls, attribution names {attr['top'][0]['key']!r}"
        )

        # /hotkeys names the shedding tenant too (the attribution plane
        # has its own endpoint, not just a ride-along in /health)
        status, body = await http_get(
            gateway.host, gateway.port, "/hotkeys?domain=tenant_sheds"
        )
        assert status.endswith("200 OK"), status
        hot = json.loads(body)
        sheds_top = hot["domains"]["tenant_sheds"]["top"]
        assert any(e["key"] == "(default)" for e in sheds_top), hot

        # storm over: the verdict must RECOVER, and must pass through
        # warn on the way down (hysteresis hold-down + slow window) —
        # a health plane that snaps burning->ok would flap the pager
        adm.clear_pressure("smoke_storm")
        deadline = asyncio.get_event_loop().time() + 20.0
        while asyncio.get_event_loop().time() < deadline:
            status, body = await http_get(gateway.host, gateway.port, "/health")
            health = json.loads(body)
            shed_slo = next(
                s for s in health["slos"] if s["name"] == "edge_shed_rate"
            )
            states_seen.append(shed_slo["state"])
            if shed_slo["state"] == "ok":
                break
            await asyncio.sleep(0.12)
        assert states_seen[-1] == "ok", (
            "edge_shed_rate never recovered to ok", states_seen,
        )
        last_burn = len(states_seen) - 1 - states_seen[::-1].index("burning")
        assert "warn" in states_seen[last_burn + 1:], (
            "recovery skipped the warn hold-down (hysteresis)", states_seen,
        )
        assert health["verdict"] == "ok", health
        note(f"health arc: {'>'.join(dict.fromkeys(states_seen))} (hysteresis held)")

        # -------- mesh-scope leg (ISSUE 18 CI gate): a second EMULATED
        # host ships its registry snapshot over a REAL rpc/tcp socket
        # (length-prefixed frames, actual loopback TCP), then
        # /metrics?scope=mesh must answer ONE honest merge: parses as
        # Prometheus text, both host= labels present, a known counter
        # SUMs exactly, and the declared-MAX oplog lag stays MAX
        from stl_fusion_tpu.diagnostics.mesh_telemetry import (
            MeshTelemetryAggregator,
            MeshTelemetryPublisher,
            MeshTelemetryService,
        )
        from stl_fusion_tpu.diagnostics.metrics import MetricsRegistry
        from stl_fusion_tpu.rpc.tcp import RpcTcpServer, tcp_client_connector

        agg = MeshTelemetryAggregator(period_s=5.0)
        gateway.mesh_telemetry = agg
        server_rpc.add_service("mesh-telemetry", MeshTelemetryService(agg))
        telem_server = await RpcTcpServer(server_rpc, ref_prefix="").start()
        global_metrics().gauge(
            "fusion_oplog_reader_lag",
            help="rows behind the oplog tail (emulated for the mesh leg)",
        ).set(4.0)
        global_metrics().set_aggregation("fusion_oplog_reader_lag", "max")

        # host h1: its own registry, its own hub, a real TCP dial
        remote_reg = MetricsRegistry()
        remote_reg.counter(
            "fusion_waves_run_total", help="emulated h1 wave counter"
        ).inc(7)
        remote_reg.gauge(
            "fusion_oplog_reader_lag", help="emulated h1 oplog lag"
        ).set(9.0)
        remote_reg.set_aggregation("fusion_oplog_reader_lag", "max")
        remote_pub = MeshTelemetryPublisher(
            member="h1", registry=remote_reg, period_s=5.0
        )
        peer_rpc = RpcHub("h1-telemetry")
        peer_rpc.client_connector = tcp_client_connector(
            "127.0.0.1", telem_server.port, client_id="h1"
        )
        reply = await remote_pub.publish_hub(peer_rpc)
        assert reply.get("ok") and "h1" in reply.get("hosts", ()), reply

        status, body = await http_get(
            gateway.host, gateway.port, "/metrics?scope=mesh"
        )
        assert status.endswith("200 OK"), status
        mesh_samples = parse_exposition(body.decode())
        local_member = agg.local_member
        waves_local = mesh_samples.get(
            f'fusion_waves_run_total{{host="{local_member}"}}'
        )
        waves_remote = mesh_samples.get('fusion_waves_run_total{host="h1"}')
        assert waves_local is not None and waves_remote == 7.0, (
            "mesh exposition must carry BOTH host labels",
            waves_local, waves_remote,
        )
        assert mesh_samples["fusion_waves_run_total"] == waves_local + 7.0, (
            "merged counter must be the EXACT sum of the per-host scrapes",
            mesh_samples["fusion_waves_run_total"], waves_local,
        )
        assert mesh_samples["fusion_oplog_reader_lag"] == 9.0, (
            "declared-MAX gauge must merge as MAX across hosts, not SUM",
            mesh_samples["fusion_oplog_reader_lag"],
        )
        assert mesh_samples.get('fusion_mesh_telemetry_stale{host="h1"}') == 0.0
        assert mesh_samples.get("fusion_mesh_telemetry_hosts_reporting") == 2.0
        note(
            f"mesh scope: {len(mesh_samples)} merged samples over "
            f"{agg.known_hosts()}; SUM + MAX semantics exact over a real "
            f"TCP snapshot"
        )

        # with the aggregator attached, /health widens to MESH scope: the
        # remote's shipped verdict folds in worst-wins, zero stale hosts
        status, body = await http_get(gateway.host, gateway.port, "/health")
        assert status.endswith("200 OK"), status
        mesh_health = json.loads(body)
        assert mesh_health["scope"] == "mesh", mesh_health
        assert mesh_health["verdict"] == "ok", mesh_health
        assert "h1" in mesh_health["hosts"], mesh_health["hosts"]
        assert mesh_health["hosts"]["h1"]["verdict"] == "ok", mesh_health
        assert mesh_health["stale"] == [], mesh_health
        await peer_rpc.stop()
        await telem_server.stop()

        from stl_fusion_tpu.graph import require_accelerator

        print(json.dumps({
            "metric": "telemetry_smoke",
            **require_accelerator("perf/telemetry_smoke.py"),
            "ok": True,
            "subscriptions": len(nodes),
            "delivery_count": int(delivery_count),
            "delivery_p50_ms": report["delivery"]["p50"],
            "delivery_p99_ms": report["delivery"]["p99"],
            "waves_recorded": report["waves"]["waves_recorded"],
            "exposition_samples": len(samples),
            "cause": cause,
            "explain_chain": explain_payload["chain"],
            "recorder_events": report["recorder"]["events_recorded"],
            "fused_depth_p50": fused_p50,
            "fused_trace_entries": len(fused_recent),
            "mesh_hosts": agg.known_hosts(),
            "mesh_samples": len(mesh_samples),
            "health_arc": list(dict.fromkeys(states_seen)),
            "mesh_health": mesh_health["verdict"],
            "shed_attribution": attr["top"][0]["key"],
        }))
        monitor.dispose()
        await gateway.stop()
        for crpc in client_rpcs:
            await crpc.stop()
        await server_rpc.stop()
        return 0
    finally:
        set_default_hub(old)


if __name__ == "__main__":
    sys.exit(asyncio.run(main()))
