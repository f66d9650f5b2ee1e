"""``table_dag``: the bare graph service on one chip, as ``chip_smoke.py``
(PR 21) builds it.

The generated power-law DAG, a ``TableBacking`` compute service on a
``FusionHub`` with a ``TpuGraphBackend``; ``bind_table_rows`` →
``declare_row_edges`` → ``warm_block_on_device`` → ``build_topo_mirror``;
``enable_nonblocking`` and ``enable_super_rounds``; a watchdog attached so
that a device fault the host loop would quietly absorb is counted. No
program is warmed here: each driver warms the ones its window uses.
"""

import threading

import numpy as np


class Deployment:
    """What a driver gets: the system under test and the plain reference's
    record of the same topology."""

    def __init__(self):
        self.n = 0
        self.oracle = None  # lib.hostgraph.HostGraph
        self.hub = self.old_hub = None
        self.backend = self.watchdog = None
        self.svc = None
        self.table = self.block = self.gdev = None
        self.pipe = self.sr = None

    def fallbacks(self) -> dict:
        """Every counted fallback of the live path; any nonzero makes the
        run incorrect (as in chip_smoke.py)."""
        sr, pipe, wd = self.sr.stats(), self.pipe.stats(), self.watchdog
        return {
            "watchdog_faults": wd.faults,
            "watchdog_fallbacks": wd.fallbacks,
            "watchdog_deadline_trips": wd.deadline_trips,
            "superround_eager_rounds": sr["eager_rounds"],
            "superround_faults": sr["faults"],
            "superround_restages": sr["restages"],
            "superround_forced_harvests": sr["journal_forced_harvests"],
            "pipeline_eager_waves": pipe["eager_waves"],
            "pipeline_chain_faults": self.pipe.chain_faults,
        }

    def fallbacks_compared(self):
        """(the fallbacks that fired, the number compared with its limit 0)."""
        fired = {k: v for k, v in self.fallbacks().items() if v}
        return fired, {"name": "fallbacks_fired", "value": sum(fired.values()), "limit": 0}

    def restore(self) -> None:
        """Recompute what waves left stale and wait for the device: the
        refresh is an asynchronous O(n) program, and a timed sample that
        follows must not be charged for it."""
        import jax

        if self.table.stale_count():
            self.backend.refresh_block_on_device(self.block)
        self.backend.flush()
        jax.device_get(self.table.values[:1])


def make_service(n: int):
    """``perf/live_path.py``'s table-backed DAG service (row i's value derives
    from a base array, the store; the device loader keeps the base table in
    HBM)."""
    from stl_fusion_tpu.core import ComputeService, TableBacking, compute_method

    class DagTable(ComputeService):
        def __init__(self, hub=None):
            super().__init__(hub)
            self.base = np.arange(n, dtype=np.float32)
            self._base_dev = None

        def load(self, ids):
            return self.base[np.asarray(ids, dtype=np.int64)]

        def load_dev(self, ids, base_dev):
            return base_dev[ids]

        def load_dev_args(self):
            # loader state rides as runtime arguments (a closure capture
            # would put the 40 MB base table into the compile payload)
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


async def build(ctx) -> Deployment:
    from lib.hostgraph import HostGraph, power_law_dag
    from lib.result import note
    from stl_fusion_tpu.core import FusionHub, memo_table_of, set_default_hub
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.resilience import WaveWatchdog

    m = ctx.m
    dep = Deployment()
    n = dep.n = int(ctx.size("nodes"))
    row_words = int(ctx.size("row_words"))
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
        dep.hub,
        node_capacity=n + 64,
        # headroom for declared churn: an edge-capacity grow would dirty the
        # device mirror and force a dense re-upload inside the window
        edge_capacity=len(src) + int(ctx.size("edge_headroom")),
    )
    # the deadline covers a cold compile: only faults degrade
    dep.watchdog = dep.backend.attach_watchdog(
        WaveWatchdog(deadline_s=float(ctx.size("watchdog_deadline_s")))
    )
    dep.svc = make_service(n)(dep.hub)
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
    note("building the topo and lat mirrors")
    with m.span("mirror_build"):
        mirror = dep.gdev.build_topo_mirror()
        # a checkout's first run writes the mirror disk cache (~1 GB) from a
        # background thread: wait for it, so that no window shares the host
        # with that write
        for thread in threading.enumerate():
            if thread.name == "mirror-cache-save":
                thread.join()
    m.values["mirror_levels"] = mirror["levels"]
    dep.pipe = dep.hub.enable_nonblocking(
        fuse_depth=int(ctx.size("fuse_depth")), max_words=row_words
    )
    dep.sr = dep.backend.enable_super_rounds(
        dep.block, depth=int(ctx.size("super_round_depth")), max_words=row_words
    )
    m.values["graph_build_s"] = (
        m.span_seconds("graph_generate") + m.span_seconds("columnar_build")
        + m.span_seconds("mirror_build")
    )
    m.values["edges"] = int(len(src))
    return dep


async def close(dep: Deployment) -> None:
    from stl_fusion_tpu.core import set_default_hub

    try:
        if dep.pipe is not None:
            dep.pipe.dispose()
        if dep.sr is not None:
            dep.sr.dispose()
    finally:
        set_default_hub(dep.old_hub)
