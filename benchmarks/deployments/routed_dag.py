"""``routed_dag``: the graph service over a mesh, as ``chip_smoke.py``'s mesh
leg (PR 21) sizes it, through the backend's own path.

``table_dag``'s service and columnar build (the generated power-law DAG, a
``TableBacking`` compute service on a ``FusionHub`` with a
``TpuGraphBackend``; ``bind_table_rows`` → ``declare_row_edges`` →
``warm_block_on_device``), then ``enable_mesh_routing`` with a shard map of
the configuration's members over a mesh of as many devices, and the routed
mirror built in set-up by ``routed_mirror()``: the graph's CSR shards on the
members' chips, the frontier exchanged by collectives. No topo or lat
mirror, no pipeline, no super-rounds: this deployment's waves are routed.
One process drives every chip. A watchdog is attached.
"""
import sys

from deployments import table_dag


class Deployment(table_dag.Deployment):
    def __init__(self):
        super().__init__()
        self.mesh = None
        self.routed = None  # parallel.routed_wave.RoutedShardedGraph
        self.build_s: dict = {}  # the set-up's spans, for the result's notes
        self._rebuilds0 = 0

    @staticmethod
    def metric(name: str) -> int:
        from stl_fusion_tpu.diagnostics.metrics import global_metrics

        return int(global_metrics().snapshot().get(name, 0))

    def fallbacks(self) -> dict:
        """Every counted fallback of the routed path; any nonzero makes the
        run incorrect."""
        wd = self.watchdog
        return {
            "watchdog_faults": wd.faults,
            "watchdog_fallbacks": wd.fallbacks,
            "watchdog_deadline_trips": wd.deadline_trips,
            # since the set-up's build: a rebuild is the last rung of the
            # patch ladder, and this window patches nothing
            "mesh_rebuilds": self.metric("fusion_mesh_rebuilds_total") - self._rebuilds0,
            "mirror_replaced": int(self.backend.routed_mirror()["graph"] is not self.routed),
            "tree_fallbacks": self.routed.tree_fallbacks,
            "hier_fallbacks": self.routed.hier_fallbacks,
            "mesh_member_relays": self.metric("fusion_mesh_member_relays_total"),
        }

    def layout_compared(self) -> dict:
        """The routed graph's resident arrays against the mesh: block ``d``
        of every array has to lie on ``mesh.devices.flat[d]``. The number
        compared is the count of arrays that do not."""
        want = [d.id for d in self.mesh.devices.flat]
        layout = self.routed.device_layout()
        wrong = sorted(name for name, ids in layout.items() if ids != want)
        self.layout = {"devices": want, "arrays": len(layout), "misplaced": wrong}
        return {"name": "layout_misplaced_arrays", "value": len(wrong), "limit": 0}


async def build(ctx) -> Deployment:
    import jax

    from lib.hostgraph import HostGraph, power_law_dag
    from lib.result import note
    from stl_fusion_tpu.cluster import ShardMap
    from stl_fusion_tpu.core import FusionHub, memo_table_of, set_default_hub
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.parallel import graph_mesh
    from stl_fusion_tpu.resilience import WaveWatchdog

    m = ctx.m
    members = int(ctx.size("members"))
    if len(jax.devices()) < members:
        # a rehearsal on the CPU's one device: say what it takes and stop
        print(f"# benchmark: {ctx.cell['name']} needs {members} devices, JAX shows "
              f"{len(jax.devices())}; a CPU rehearsal gets them with "
              f"XLA_FLAGS=--xla_force_host_platform_device_count={members}",
              file=sys.stderr)
        raise SystemExit(2)
    dep = Deployment()
    n = dep.n = int(ctx.size("nodes"))
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
        dep.hub, node_capacity=n + 64,
        edge_capacity=len(src) + int(ctx.size("edge_headroom")),
    )
    dep.watchdog = dep.backend.attach_watchdog(
        WaveWatchdog(deadline_s=float(ctx.size("watchdog_deadline_s")))
    )
    dep.svc = table_dag.make_service(n)(dep.hub)
    dep.hub.add_service(dep.svc, "dag")
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
    note(f"routed mirror: {int(ctx.size('shards'))} shards over {members} members, "
         f"exchange {ctx.size('exchange')}")
    with m.span("mirror_build"):
        dep.mesh = graph_mesh(n_devices=members)
        dep.backend.enable_mesh_routing(
            ShardMap.initial([f"m{i}" for i in range(members)],
                             n_shards=int(ctx.size("shards"))),
            mesh=dep.mesh, exchange=ctx.size("exchange"),
            exchange_async=bool(ctx.size("exchange_async")),
        )
        dep.routed = dep.backend.routed_mirror()["graph"]
        jax.block_until_ready(dep.routed.g_edst)
    dep._rebuilds0 = dep.metric("fusion_mesh_rebuilds_total")
    dep.build_s = {
        name: m.span_seconds(name)
        for name in ("graph_generate", "columnar_build", "mirror_build")
    }
    m.values["graph_build_s"] = sum(dep.build_s.values())
    m.values["edges"] = int(len(src))
    return dep


async def close(dep: Deployment) -> None:
    from stl_fusion_tpu.core import set_default_hub

    set_default_hub(dep.old_hub)
