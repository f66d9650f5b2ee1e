"""``cart_served``: upstream's first sample (``samples/HelloCart``: products,
carts, ``GetTotal``, an ``Edit`` command, clients that watch totals) as a
table-backed service at TPC-C's scale, SERVED as ``served_dag`` serves its
graph: one-member ``ClusterCommander`` over an ``InMemoryOperationLog``,
``RpcHub`` with the fan-out index, client hubs over
``RpcTestTransport(wire_codec=True)``, the round driver's ``drain()`` on a
fixed-rate timer (``served_dag``'s own drain loop).

The service ``shop`` has three table-backed compute methods on ONE
``TpuGraphBackend``:

- ``product(i)``: the stored price. HOT: its device loader reads the price
  store, and a total must never be recomputed from a stale price row;
- ``cart(c)``: the stored line count of the cart (never written here);
- ``total(c)``: DERIVED, ``sum(price(product) * quantity)`` over the cart's
  lines, and HOT. On the scalar road its body awaits ``cart(c)`` and each
  line's ``product(p)``, so its dependencies are captured as upstream's
  ``GetTotal`` captures them; on the columnar road its device loader reads
  the product table's device values and the ``[carts, 15]`` line tables
  (pad lines: quantity 0 times the price of product 0).

Edges ``product -> total`` per line and ``cart -> total`` are declared in
bulk. The command ``Edit(product, delta)`` raises the stored price by a whole
number; its invalidation replay touches ``product(i)``. Because both written
and derived tables are hot, everything a command's wave invalidates is fresh
again on the device, and valid again in the graph, before the next wave: a
second edit of the same product cascades as far as the first.

No super-round is installed (no burst traffic here). The key pool is the
configuration's (``pool_*`` sizes): products whose fan-out lies in a band,
each with its first carts by id; who subscribes to which of those carts'
totals is the traffic's.
"""
import asyncio
import threading

import numpy as np

from deployments import served_dag


class Deployment(served_dag.Deployment):
    def __init__(self):
        super().__init__()
        self.data = None  # lib.cartgraph.CartData: the benchmark's own record
        self.Edit = None
        self.tables: dict = {}  # method name -> MemoTable
        self.blocks: dict = {}  # method name -> RowBlock
        self.pool_products = self.pool_watched = self.pool_fanout = None
        self.rebuilds_at_build = 0

    def fallbacks(self) -> dict:
        """Every counted fallback of the live path; any nonzero makes the
        run incorrect. No super-round here, so none of its counters."""
        pipe, wd, gdev = self.pipe.stats(), self.watchdog, self.gdev
        mirror = gdev._topo_mirror or {}
        return {
            "watchdog_faults": wd.faults,
            "watchdog_fallbacks": wd.fallbacks,
            "watchdog_deadline_trips": wd.deadline_trips,
            "pipeline_eager_waves": pipe["eager_waves"],
            "pipeline_chain_faults": self.pipe.chain_faults,
            "pipeline_lat_overflow_waves": pipe["lat_overflow_waves"],
            "hot_refresh_block_fallbacks": self.backend.hot_refresh_block_fallbacks,
            "mirror_rebuilds_since_build": gdev.mirror_rebuilds - self.rebuilds_at_build,
            "mirror_patch_log_broken": int(gdev._mirror_deltas is None),
            "lat_mirror_broken": int(mirror.get("lat") is None),
            "outbox_drain_faults": sum(ob.drain_faults for ob in self._outboxes()),
        }


class Client:
    """One subscribed client, as ``served_dag``'s: its own fusion hub and RPC
    hub over a codec-faithful in-memory link, with a proxy of the ``shop``
    service. It reads totals."""

    def __init__(self, i: int, server_rpc):
        from stl_fusion_tpu.client import compute_client, install_compute_call_type
        from stl_fusion_tpu.core import FusionHub
        from stl_fusion_tpu.rpc import RpcHub, RpcTestTransport

        self.i = i
        self.rpc = RpcHub(f"client-{i}")
        install_compute_call_type(self.rpc)
        self.transport = RpcTestTransport(self.rpc, server_rpc, wire_codec=True)
        self.proxy = compute_client("shop", self.rpc, FusionHub(), peer_ref=f"c{i}")

    async def read(self, cart: int):
        from stl_fusion_tpu.core import capture

        computed = await capture(lambda: self.proxy.total(cart))
        return computed.value, computed


def make_shop(data):
    """The service and its command."""
    import dataclasses

    from stl_fusion_tpu.commands import command_handler
    from stl_fusion_tpu.core import (
        ComputeService,
        TableBacking,
        compute_method,
        is_invalidating,
        memo_table_of,
    )
    from stl_fusion_tpu.utils.serialization import wire_type

    products, carts = data.products, data.carts

    @wire_type("BenchCartEdit")
    @dataclasses.dataclass(frozen=True)
    class Edit:
        product: int
        delta: float

        def shard_key(self):
            return f"product-{self.product}"

    class Shop(ComputeService):
        def __init__(self, hub=None):
            super().__init__(hub)
            # the store: prices (written by Edit), the carts' lines (fixed)
            self.price = data.price.astype(np.float32)
            self.ol_cnt = data.ol_cnt
            self.line_product = data.line_product
            self.line_qty = data.line_qty.astype(np.float32)
            self._price_dev = self._cnt_dev = self._lines_dev = None

        # -- host loaders (MemoTable.refresh, read_batch)
        def load_products(self, ids):
            return self.price[np.asarray(ids, dtype=np.int64)]

        def load_carts(self, ids):
            return self.ol_cnt[np.asarray(ids, dtype=np.int64)].astype(np.float32)

        def load_totals(self, ids):
            ids = np.asarray(ids, dtype=np.int64)
            return (self.price[self.line_product[ids]] * self.line_qty[ids]).sum(
                axis=1, dtype=np.float32
            )

        # -- device loaders: state rides as runtime arguments
        def products_dev(self, ids, price):
            return price[ids]

        def products_dev_args(self):
            if self._price_dev is None:  # re-made after an Edit (400 KB)
                import jax.numpy as jnp

                self._price_dev = jnp.asarray(self.price)
            return (self._price_dev,)

        def carts_dev(self, ids, cnt):
            return cnt[ids]

        def carts_dev_args(self):
            if self._cnt_dev is None:
                import jax.numpy as jnp

                self._cnt_dev = jnp.asarray(self.ol_cnt.astype(np.float32))
            return (self._cnt_dev,)

        def totals_dev(self, ids, prices, line_product, line_qty):
            return (prices[line_product[ids]] * line_qty[ids]).sum(axis=1)

        def totals_dev_args(self):
            if self._lines_dev is None:
                import jax.numpy as jnp

                self._lines_dev = (
                    jnp.asarray(self.line_product), jnp.asarray(self.line_qty)
                )
            # the product TABLE's device values, read fresh each time: the
            # refresh of the product rows comes first and replaces the array
            return (memo_table_of(self.product).values, *self._lines_dev)

        @compute_method(table=TableBacking(
            rows=products, batch="load_products", device_batch="products_dev",
            device_args="products_dev_args", hot=True,
        ))
        async def product(self, i: int) -> float:
            return float(self.price[i])

        @compute_method(table=TableBacking(
            rows=carts, batch="load_carts", device_batch="carts_dev",
            device_args="carts_dev_args",
        ))
        async def cart(self, c: int) -> float:
            return float(self.ol_cnt[c])

        @compute_method(table=TableBacking(
            rows=carts, batch="load_totals", device_batch="totals_dev",
            device_args="totals_dev_args", hot=True,
        ))
        async def total(self, c: int) -> float:
            # upstream's GetTotal: the cart, then each of its products
            lines = int(await self.cart(c))
            total = 0.0
            for j in range(lines):
                price = await self.product(int(self.line_product[c, j]))
                total += price * float(self.line_qty[c, j])
            return total

        @command_handler
        async def edit(self, command: Edit):
            if is_invalidating():
                await self.product(command.product)
                return
            self.price[command.product] += np.float32(command.delta)
            self._price_dev = None
            return float(self.price[command.product])

    return Shop, Edit


async def build(ctx) -> Deployment:
    from lib import cartgraph
    from lib.result import note
    from stl_fusion_tpu.core import TableBacking

    if "hot" not in getattr(TableBacking, "__slots__", ()):
        # a program that cannot keep a table hot leaves every total invalid
        # after its first wave: say so at once, before anything is generated
        note("this checkout's TableBacking has no hot declaration (a bound "
             "table kept fresh on the device after every wave): "
             "hellocart-w100-1c cannot run on it")
        raise SystemExit(3)
    from stl_fusion_tpu.client import install_compute_call_type
    from stl_fusion_tpu.commands import ClusterCommander
    from stl_fusion_tpu.core import FusionHub, memo_table_of, set_default_hub
    from stl_fusion_tpu.graph import TpuGraphBackend
    from stl_fusion_tpu.oplog import (
        InMemoryOperationLog,
        LocalChangeNotifier,
        attach_operation_log,
    )
    from stl_fusion_tpu.resilience import WaveWatchdog
    from stl_fusion_tpu.rpc import RpcHub, install_compute_fanout

    m = ctx.m
    dep = Deployment()
    products, carts = int(ctx.size("products")), int(ctx.size("carts"))
    graph_seed = int(ctx.size("graph_seed"))
    note(f"generating {products:,} products and {carts:,} carts (graph seed {graph_seed})")
    with m.span("graph_generate"):
        data = dep.data = cartgraph.generate(products, carts, graph_seed)
        # node ids follow the bind order: products, carts, totals
        src, dst = cartgraph.edges(data, 0, products, products + carts)
    n = dep.n = products + 2 * carts
    dep.hub = FusionHub()
    dep.old_hub = set_default_hub(dep.hub)
    dep.backend = TpuGraphBackend(
        dep.hub,
        node_capacity=n + 64,
        edge_capacity=len(src) + int(ctx.size("edge_headroom")),
    )
    dep.watchdog = dep.backend.attach_watchdog(
        WaveWatchdog(deadline_s=float(ctx.size("watchdog_deadline_s")))
    )
    service, dep.Edit = make_shop(data)
    dep.svc = service(dep.hub)
    dep.hub.add_service(dep.svc, "shop")
    dep.hub.commander.add_service(dep.svc)
    dep.log_store = InMemoryOperationLog()
    dep.reader = attach_operation_log(
        dep.hub.commander, dep.log_store, LocalChangeNotifier()
    )
    note("columnar build (three tables bound, edges declared, device warm)")
    with m.span("columnar_build"):
        backend = dep.backend
        for name in ("product", "cart", "total"):
            dep.tables[name] = memo_table_of(getattr(dep.svc, name))
            dep.blocks[name] = backend.bind_table_rows(dep.tables[name])
        if [dep.blocks[k].base for k in ("product", "cart", "total")] != [
            0, products, products + carts
        ]:
            raise RuntimeError("the blocks do not lie where the edges were made for")
        lines = len(src) - carts
        backend.declare_row_edges(
            dep.blocks["product"], src[:lines], dep.blocks["total"],
            dst[:lines] - (products + carts),
        )
        backend.declare_row_edges(
            dep.blocks["cart"], src[lines:] - products, dep.blocks["total"],
            dst[lines:] - (products + carts),
        )
        for name in ("product", "cart", "total"):  # a total reads warm prices
            backend.warm_block_on_device(dep.blocks[name])
        backend.flush()
    if backend.node_count != n or any(t.stale_count() for t in dep.tables.values()):
        raise RuntimeError("the built graph is not the declared one")
    dep.gdev = backend.graph
    note("building the topo and lat mirrors")
    with m.span("mirror_build"):
        mirror = dep.gdev.build_topo_mirror()
        for thread in threading.enumerate():  # as table_dag: no shared window
            if thread.name == "mirror-cache-save":
                thread.join()
    m.values["mirror_levels"] = mirror["levels"]
    dep.rebuilds_at_build = dep.gdev.mirror_rebuilds
    dep.pipe = dep.hub.enable_nonblocking(
        fuse_depth=int(ctx.size("fuse_depth")), max_words=int(ctx.size("row_words"))
    )
    backend.warm_hot_refresh()
    m.values["graph_build_s"] = (
        m.span_seconds("graph_generate") + m.span_seconds("columnar_build")
        + m.span_seconds("mirror_build")
    )
    m.values["edges"] = int(len(src))
    m.values["mirror_rows"] = int(mirror["n_tot"])
    m.values["lat_rows"] = int(mirror["lat"]["n_tot"])
    del src, dst

    with m.span("pool"):
        dep.pool_products, dep.pool_watched, dep.pool_fanout = cartgraph.choose_pool(
            data, int(ctx.size("pool_products")), int(ctx.size("pool_seed")),
            int(ctx.size("pool_fanout_min")), int(ctx.size("pool_fanout_max")),
            int(ctx.size("pool_watched_carts")),
        )
    note(f"key pool: {len(dep.pool_products)} products, fan-out "
         f"{dep.pool_fanout.min()}..{dep.pool_fanout.max()} carts "
         f"(mean {dep.pool_fanout.mean():.1f})")
    dep.commander = ClusterCommander(
        dep.hub.commander, member_id="m0", log_store=dep.log_store
    )
    dep.server_rpc = RpcHub("server")
    install_compute_call_type(dep.server_rpc)
    dep.server_rpc.add_service("shop", dep.svc)
    install_compute_fanout(dep.server_rpc, backend)
    with m.span("clients"):
        dep.clients = [
            Client(i, dep.server_rpc) for i in range(int(ctx.size("clients")))
        ]
    dep._drainer = asyncio.get_running_loop().create_task(
        dep._drain_loop(float(ctx.size("drain_tick_ms")) / 1e3)
    )
    return dep


close = served_dag.close
