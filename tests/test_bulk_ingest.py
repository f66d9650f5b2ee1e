"""Columnar bulk ingest (VERDICT r3 #2): table-backed services register
their dense key space as ONE contiguous block of graph nodes, declare
dependency edges in bulk numpy, and cascade by row — graph construction at
array speed instead of one Python object per node. The reference absorbs
registrations one ``Register`` call at a time
(src/Stl.Fusion/ComputedRegistry.cs:72-105); this is the TPU-native bulk
equivalent, with scalar ``@compute_method`` nodes adopting row node ids so
the two views cascade as one logical node."""
import numpy as np
import pytest

from stl_fusion_tpu.core import (
    ComputeService,
    FusionHub,
    TableBacking,
    capture,
    compute_method,
    invalidating,
    memo_table_of,
    set_default_hub,
)
from stl_fusion_tpu.graph import TpuGraphBackend


class Chain(ComputeService):
    """Row i depends on row i-1 (declared in bulk); values from a dict so
    tests can mutate source truth."""

    def __init__(self, hub=None, n=64):
        super().__init__(hub)
        self.db = {i: float(i) for i in range(n)}
        self.loads = 0

    def load(self, ids):
        self.loads += len(ids)
        return np.array([self.db[int(i)] for i in ids], dtype=np.float32)

    @compute_method(table=TableBacking(rows=64, batch="load"))
    async def val(self, i: int) -> float:
        return self.db[i]


def bound_chain(n=64):
    hub = FusionHub()
    backend = TpuGraphBackend(hub, node_capacity=256, edge_capacity=1024)
    svc = Chain(hub, n)
    hub.add_service(svc)
    table = memo_table_of(svc.val)
    block = backend.bind_table_rows(table)
    # chain topology: i-1 (used) -> i (dependent)
    backend.declare_row_edges(block, np.arange(n - 1), block, np.arange(1, n))
    return hub, backend, svc, table, block


def test_bind_allocates_contiguous_block_and_flushes_edges():
    hub, backend, svc, table, block = bound_chain()
    assert block.n_rows == 64 and backend.node_count == 64
    backend.flush()
    assert backend.edge_count == 63


def test_cascade_rows_batch_reaches_transitive_dependents():
    hub, backend, svc, table, block = bound_chain()
    table.read_batch(np.arange(64))  # warm all rows
    assert table.stale_count() == 0
    total = backend.cascade_rows_batch(block, [10])
    # row 10 and every dependent 11..63 go stale in one wave
    assert total == 54
    assert table.stale_count() == 54
    stale = np.nonzero(table._stale_host)[0]
    np.testing.assert_array_equal(stale, np.arange(10, 64))
    # refresh through the loader on next read — and the device invalid
    # bits clear with NO epoch bump (declared topology survives churn)
    svc.db[10] = 100.0
    vals = np.asarray(table.read_batch([10, 63]))
    assert vals[0] == 100.0
    table.read_batch(np.arange(64))  # refresh the remaining stale rows
    assert table.stale_count() == 0
    backend.flush()
    assert not backend.graph.invalid_mask().any()
    # second cascade still follows the declared edges
    assert backend.cascade_rows_batch(block, [62]) == 2


def test_host_led_table_invalidate_mirrors_and_cascades():
    hub, backend, svc, table, block = bound_chain()
    table.read_batch(np.arange(64))
    table.invalidate([5, 7])  # host-led mark; closure lands at next flush
    backend.flush()
    mask = backend.graph.invalid_mask()
    assert mask[5] and mask[7]
    assert mask[6] and mask[63]  # declared dependents cascaded (5→6→…→63)
    assert mask.sum() == 59 and not mask[:5].any()


async def test_scalar_adoption_shares_row_node():
    hub, backend, svc, table, block = bound_chain()
    old = set_default_hub(hub)
    try:
        table.read_batch(np.arange(64))
        assert await svc.val(20) == 20.0  # scalar node adopts row 20's nid
        node = await capture(lambda: svc.val(20))
        assert backend.id_for(node) == block.base + 20
        assert backend.node_count == 64  # no new node allocated
        # cascading a declared dependency reaches the scalar twin
        backend.cascade_rows_batch(block, [19])
        assert not node.is_consistent  # pending-aware probe
        # and the table rows went stale vectorized
        assert table._stale_host[19] and table._stale_host[20]
    finally:
        set_default_hub(old)


async def test_scalar_recompute_redeclares_row_in_edges():
    hub, backend, svc, table, block = bound_chain()
    old = set_default_hub(hub)
    try:
        table.read_batch(np.arange(64))
        assert await svc.val(30) == 30.0
        # scalar recompute: epoch bump would kill declared in-edges; the
        # backend re-declares row 30's in-edges at the new epoch
        svc.db[30] = 300.0
        with invalidating():
            await svc.val(30)
        assert await svc.val(30) == 300.0
        node = await capture(lambda: svc.val(30))
        backend.cascade_rows_batch(block, [29])
        assert not node.is_consistent, "declared in-edge died on recompute"
        assert table._stale_host[30]
    finally:
        set_default_hub(old)


def test_cascade_rows_lanes_matches_dense_oracle():
    rng = np.random.default_rng(3)
    n = 200
    hub = FusionHub()
    backend = TpuGraphBackend(hub, node_capacity=512, edge_capacity=2048)
    svc = ChainN(hub, n)
    hub.add_service(svc)
    table = memo_table_of(svc.val)
    block = backend.bind_table_rows(table)
    # random DAG: src < dst
    dst = rng.integers(1, n, size=400)
    src = (rng.random(400) * dst).astype(np.int64)
    backend.declare_row_edges(block, src, block, dst)
    table.read_batch(np.arange(n))

    groups = [rng.choice(n, size=4, replace=False).tolist() for _ in range(40)]
    counts = backend.cascade_rows_lanes(block, groups)

    # oracle: per-group dense BFS from a clean graph
    adj_starts = np.zeros(n + 1, dtype=np.int64)
    np.add.at(adj_starts[1:], src, 1)
    adj_starts = np.cumsum(adj_starts)
    order = np.argsort(src, kind="stable")
    adj_dst = dst[order]

    def bfs(seeds):
        seen = np.zeros(n, dtype=bool)
        frontier = list(seeds)
        for s in frontier:
            seen[s] = True
        while frontier:
            nxt = []
            for u in frontier:
                for v in adj_dst[adj_starts[u] : adj_starts[u + 1]]:
                    if not seen[v]:
                        seen[v] = True
                        nxt.append(int(v))
            frontier = nxt
        return int(seen.sum())

    for gi, g in enumerate(groups):
        assert counts[gi] == bfs(g), (gi, counts[gi], bfs(g))
    # the union landed in the table's stale set
    assert table.stale_count() == int(backend.graph.invalid_mask().sum())


class ChainN(ComputeService):
    def __init__(self, hub=None, n=200):
        super().__init__(hub)
        self.n = n

    def load(self, ids):
        return np.asarray(ids, dtype=np.float32)

    @compute_method(table=TableBacking(rows=200, batch="load"))
    async def val(self, i: int) -> float:
        return float(i)


def test_bulk_ingest_throughput_smoke():
    """The point of the feature: building a 100K-node graph through the
    bound-table path takes array time, not object time."""
    import time

    n = 100_000
    hub = FusionHub()
    backend = TpuGraphBackend(hub, node_capacity=n, edge_capacity=4 * n)

    def load(ids):
        return np.asarray(ids, dtype=np.float32)

    from stl_fusion_tpu.ops.memo_table import MemoTable

    table = MemoTable(n, load)
    t0 = time.perf_counter()
    block = backend.bind_table_rows(table)
    rng = np.random.default_rng(0)
    dst = rng.integers(1, n, size=3 * n)
    src = (rng.random(3 * n) * dst).astype(np.int64)
    backend.declare_row_edges(block, src, block, dst)
    table.read_batch(np.arange(n))  # warm every row through the loader
    backend.flush()
    build_s = time.perf_counter() - t0
    rate = n / build_s
    assert backend.node_count == n and backend.edge_count == 3 * n
    assert table.stale_count() == 0
    assert rate > 100_000, f"bulk ingest ran at {rate:.0f} nodes/s"


def test_partial_bind_guards_out_of_block_rows():
    """Review r4: a partial bind (n_rows < table.n_rows) must not journal
    invalid/clear marks for rows past the block — those node ids belong (or
    will belong) to unrelated nodes."""
    from stl_fusion_tpu.ops.memo_table import MemoTable

    hub = FusionHub()
    backend = TpuGraphBackend(hub, node_capacity=64, edge_capacity=64)
    table = MemoTable(8, lambda ids: np.asarray(ids, dtype=np.float32))
    block = backend.bind_table_rows(table, n_rows=4)
    other = backend.graph.add_nodes(4)  # nodes right after the block
    backend._ensure_host_masks()
    table.read_batch(np.arange(8))
    table.invalidate([2, 6])  # row 6 is OUTSIDE the block
    backend.flush()
    mask = backend.graph.invalid_mask()
    assert mask[block.base + 2]
    assert not mask[other].any(), "out-of-block row corrupted a foreign node"
    # refresh of an out-of-block row must not CLEAR a foreign node's bit
    backend.graph.mark_invalid(np.array([other[1]]))  # other[1] == base+5
    table.invalidate([5])
    table.read_batch([5])  # refresh row 5 (outside the block)
    backend.flush()
    assert backend.graph.invalid_mask()[other[1]], "foreign invalid bit cleared"


def test_cascade_rows_rejects_out_of_range():
    hub, backend, svc, table, block = bound_chain()
    import pytest

    with pytest.raises(ValueError):
        backend.cascade_rows_batch(block, [64])
    with pytest.raises(ValueError):
        backend.cascade_rows_lanes(block, [[0], [-1]])


def test_clear_declared_row_edges_redeclares():
    """Review r4: declarations accumulate; clear_declared_row_edges drops a
    row's declared in-edges (log + live graph) so redeclaration replaces
    instead of unioning."""
    hub, backend, svc, table, block = bound_chain()
    table.read_batch(np.arange(64))
    # rewire row 40: was 39 -> 40; becomes 10 -> 40
    backend.clear_declared_row_edges(block, [40])
    backend.declare_row_edges(block, np.array([10]), block, np.array([40]))
    backend.flush()
    # old topology severed: cascading 39 no longer reaches 40
    total = backend.cascade_rows_batch(block, [39])
    assert not table._stale_host[40]
    # new topology live: cascading 10 reaches 40 (and dependents 41..63)
    total2 = backend.cascade_rows_batch(block, [10])
    assert table._stale_host[40] and table._stale_host[63]
    # the declaration log reflects the rewire (one in-edge for row 40)
    starts, src, _included = block._declared_csr()
    s, e = int(starts[40]), int(starts[41])
    assert e - s == 1 and int(src[s]) == block.base + 10


def test_host_led_invalidate_cascades_to_declared_dependents():
    """Review r4 (confirmed under-invalidation): table.invalidate must
    CASCADE through the declared row topology — the reference's rule that
    invalidation always walks dependents. The closure lands at the next
    flush; the marked rows themselves are not re-staled (a refresh between
    mark and flush sticks)."""
    hub, backend, svc, table, block = bound_chain()
    table.read_batch(np.arange(64))
    table.invalidate([10])           # host-led mark
    svc.db[10] = 100.0
    table.read_batch([10])           # refresh BEFORE the flush: must stick
    backend.flush()                  # icasc expands the declared closure
    assert not table._stale_host[10]  # the refresh was not clobbered
    assert table._stale_host[11] and table._stale_host[63]
    mask = backend.graph.invalid_mask()
    assert mask[11] and mask[63]
    # and a cascade_rows from an already-invalid seed still conducts
    backend.graph.clear_invalid()
    table.read_batch(np.nonzero(table._stale_host)[0])
    table.invalidate([20])
    backend.flush()
    assert backend.cascade_rows_batch(block, [20]) == 0  # closure already done
    assert table._stale_host[21] and table._stale_host[63]


def test_icasc_mark_refresh_then_upstream_mark_in_one_flush():
    """Review r4 (confirmed): mark S, refresh S, then mark an UPSTREAM row
    T — all in one flush window. S must come out STALE (it sits in T's
    declared closure); the deferred-expansion batching must not let the
    refresh restore clobber it."""
    hub, backend, svc, table, block = bound_chain()
    table.read_batch(np.arange(64))
    # declared chain: i-1 -> i, so 9's closure includes 10
    table.invalidate([10])        # mark S=10
    svc.db[10] = 100.0
    table.read_batch([10])        # refresh S before the flush
    table.invalidate([9])         # mark upstream T=9 (10 is its dependent)
    backend.flush()
    assert table._stale_host[10], "refreshed row escaped its dependency's cascade"
    assert table._stale_host[11] and table._stale_host[63]
    assert not table._stale_host[9] or True  # 9 itself stays marked (it led)
    mask = backend.graph.invalid_mask()
    assert mask[10] and mask[9]


def test_monitor_counts_no_phantom_hits_on_misses(fresh_hub=None):
    """Review r4: the post-invoke hot-cache probe must not fire on_access —
    a 100%-miss workload must report hit_ratio ~0."""
    import asyncio

    from stl_fusion_tpu.diagnostics import FusionMonitor

    async def run():
        hub = FusionHub()
        old = set_default_hub(hub)
        monitor = FusionMonitor(hub)
        try:

            class S(ComputeService):
                @compute_method
                async def get(self, k: int) -> int:
                    return k

            svc = S(hub)
            for i in range(50):  # distinct keys: all misses
                await svc.get(i)
            assert monitor.registrations == 50
            assert monitor.hit_ratio < 0.1, monitor.report()
        finally:
            monitor.dispose()
            set_default_hub(old)

    asyncio.run(run())


def test_hot_cache_evicts_collected_entries():
    """Review r4: dead weakrefs must not accumulate — collection evicts."""
    import asyncio
    import gc

    async def run():
        hub = FusionHub()
        old = set_default_hub(hub)
        try:
            class S(ComputeService):
                @compute_method
                async def get(self, k: int) -> int:
                    return k

            svc = S(hub)
            for i in range(64):
                await svc.get(i)
            hot_attr = [a for a in svc.__dict__ if a.startswith("_fusion_hot_")][0]
            hot = svc.__dict__[hot_attr]
            assert len(hot) == 64
            hub.registry.clear() if hasattr(hub.registry, "clear") else None
            # drop all strong refs the registry holds weakly; keep-alive
            # timers may pin some — clear them through the hub timeouts
            hub.timeouts.clear() if hasattr(hub.timeouts, "clear") else None
            gc.collect()
            # at minimum, SOME entries evicted once nodes are collected;
            # the invariant under test: no dead weakref stays behind
            dead = [k for k, r in hot.items() if r() is None]
            assert not dead, f"{len(dead)} dead hot entries leaked"
        finally:
            set_default_hub(old)

    asyncio.run(run())


async def test_device_loader_warm_and_refresh():
    """r5: TableBacking(device_batch=...) — cold-start warm and stale-row
    recompute run entirely on device (loader state as runtime args), with
    host bookkeeping matching the host-path semantics."""
    import jax.numpy as jnp

    from stl_fusion_tpu.core import TableBacking, compute_method, memo_table_of

    n = 64

    class DevSvc(ComputeService):
        def __init__(self, hub=None):
            super().__init__(hub)
            self.base = np.arange(n, dtype=np.float32)
            self._dev = jnp.asarray(self.base)

        def load(self, ids):
            return self.base[np.asarray(ids, dtype=np.int64)]

        def load_dev(self, ids, base_dev):
            return base_dev[ids] * 2.0

        def dev_args(self):
            return (self._dev,)

        @compute_method(
            table=TableBacking(
                rows=n, batch="load", device_batch="load_dev", device_args="dev_args"
            )
        )
        async def val(self, i: int) -> float:
            return float(self.base[i])

    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub, node_capacity=n, edge_capacity=8 * n)
        svc = DevSvc(hub)
        hub.add_service(svc)
        table = memo_table_of(svc.val)
        block = backend.bind_table_rows(table)
        backend.declare_row_edges(block, np.arange(n - 1), block, np.arange(1, n))
        assert backend.warm_block_on_device(block) == n
        assert table.stale_count() == 0
        np.testing.assert_allclose(np.asarray(table.values), svc.base * 2.0)
        # cascade marks rows stale; the device refresh recomputes them
        svc._dev = jnp.asarray(svc.base + 100.0)
        total = backend.cascade_rows_batch(block, [50])
        assert total == 14 and table.stale_count() == 14
        assert backend.refresh_block_on_device(block) == 14
        assert table.stale_count() == 0
        vals = np.asarray(table.values)
        np.testing.assert_allclose(vals[:50], svc.base[:50] * 2.0)  # untouched
        np.testing.assert_allclose(vals[50:], (svc.base[50:] + 100.0) * 2.0)
        assert not backend.graph.invalid_mask().any()  # device state cleared
        assert not backend.graph._h_invalid.any()
    finally:
        set_default_hub(old)


async def test_cascade_rows_batch_seq_matches_sequential_hub_level():
    """cascade_rows_batch_seq through the BACKEND: sequential semantics,
    table rows stale, per-batch counts — identical to M separate calls."""
    n = 200
    hub = FusionHub()
    old = set_default_hub(hub)
    try:
        backend = TpuGraphBackend(hub, node_capacity=n, edge_capacity=8 * n)
        svc = ChainN(hub, n)
        hub.add_service(svc)
        table = memo_table_of(svc.val)
        block = backend.bind_table_rows(table)
        backend.declare_row_edges(
            block, np.arange(n - 1), block, np.arange(1, n)
        )
        table.read_batch(np.arange(n))
        backend.flush()
        backend.graph.build_topo_mirror()
        counts = backend.cascade_rows_batch_seq(block, [[150], [100], [150]])
        # chain semantics: [150] stales 150..199 (50); [100] stales
        # 100..149 (50 — rows ≥150 already stale); [150] again: 0 newly
        assert counts.tolist() == [50, 50, 0]
        assert table.stale_count() == 100
        assert bool(table._stale_host[100]) and not bool(table._stale_host[99])
    finally:
        set_default_hub(old)


# ------------------------------------------------- the wave's echo (ISSUE 31)
def icasc_ids(backend):
    parts = [p for kind, p in backend._journal if kind == "icasc"]
    return sorted(set(np.concatenate(parts).tolist())) if parts else []


async def watched_twins(svc, rows, hits):
    """Scalar twins of ``rows`` with an invalidation observer each: the
    eager tier of the next wave that reaches them."""
    twins = [await capture(lambda r=r: svc.val(r)) for r in rows]
    for twin in twins:
        twin.on_invalidated(hits.append)
    return twins


async def test_a_wave_over_watched_twins_journals_nothing():
    """The application of a wave is no host-led invalidation: the watched
    twins' table marks (``mark_row_stale`` -> ``table.invalidate`` ->
    ``on_inv``) are dropped and counted, the rows are stale on the table all
    the same, and the next flush finds nothing to do."""
    hub, backend, svc, table, block = bound_chain()
    old = set_default_hub(hub)
    try:
        table.read_batch(np.arange(64))
        hits: list = []
        twins = await watched_twins(svc, [50, 51, 52], hits)
        unwatched = await capture(lambda: svc.val(53))
        backend.flush()
        version = table.version
        assert backend.cascade_rows_batch(block, [50]) == 14
        assert hits == twins  # the eager tier fired, in wave order
        assert not unwatched.is_consistent  # the lazy tier: pending
        assert backend._journal == []
        assert backend.wave_echo_marks_dropped == 3  # ids, one a twin
        assert backend._collect_metrics()["fusion_wave_echo_marks_dropped_total"] == 3
        # the table's own side of the mark is what it was
        np.testing.assert_array_equal(np.nonzero(table._stale_host)[0], np.arange(50, 64))
        assert table.version == version + 1 + 3  # the wave's mark, then one a twin
        mask = np.asarray(backend.graph.invalid_mask()).copy()
        waves, total = backend.graph.lat_waves, backend.device_invalidations
        backend.flush()
        np.testing.assert_array_equal(np.asarray(backend.graph.invalid_mask()), mask)
        assert (backend.graph.lat_waves, backend.device_invalidations) == (waves, total)
    finally:
        set_default_hub(old)


@pytest.mark.parametrize(
    "kind", ["table_invalidate", "invalidating_replay", "invalidate_all", "lazy_materialization"]
)
async def test_host_led_marks_journal_their_cascade_as_before(kind):
    """What is NOT a wave's own application keeps its ``icasc``."""
    hub, backend, svc, table, block = bound_chain()
    old = set_default_hub(hub)
    try:
        table.read_batch(np.arange(64))
        twin = await capture(lambda: svc.val(30))
        backend.flush()
        if kind == "table_invalidate":
            table.invalidate([5, 30])
            want = [5, 30]
        elif kind == "invalidating_replay":
            with invalidating():
                await svc.val(30)
            want = [30]
        elif kind == "invalidate_all":
            table.invalidate_all()
            want = list(range(64))
        else:
            # an unwatched twin the wave left pending, materialized by a
            # host-led invalidate outside any wave application
            assert backend.cascade_rows_batch(block, [29]) == 35
            assert backend._journal == []
            twin.invalidate()
            want = [30]
        assert icasc_ids(backend) == want
        assert backend.wave_echo_marks_dropped == 0
        assert not twin.is_consistent
        backend.flush()
        assert np.asarray(backend.graph.invalid_mask())[min(want):64].all()
    finally:
        set_default_hub(old)


async def test_a_handler_marking_another_row_during_application_still_journals():
    """Only the node being applied is an echo: a mixed batch from inside a
    watched twin's handler loses that node's id and keeps the other."""
    hub, backend, svc, table, block = bound_chain()
    old = set_default_hub(hub)
    try:
        table.read_batch(np.arange(64))
        twin = await capture(lambda: svc.val(60))
        twin.on_invalidated(lambda _c: table.invalidate([60, 3]))
        backend.flush()
        assert backend.cascade_rows_batch(block, [60]) == 4
        assert len(backend._journal) == 1 and icasc_ids(backend) == [3]
        assert backend.wave_echo_marks_dropped == 2  # row 60 twice: its own mark, the handler's
        backend.flush()
        assert np.asarray(backend.graph.invalid_mask())[3:64].all()
    finally:
        set_default_hub(old)


@pytest.mark.parametrize("watched", [True, False])
async def test_rows_declared_under_an_invalid_row_wait_for_the_next_wave(watched):
    """The one observable difference of dropping the echo (ISSUE 31): a row
    declared as a dependent of a row AFTER a wave invalidated it and BEFORE
    the next flush is not invalidated by that flush, whether the invalid
    row has a watched twin or not (the echo's ``icasc`` used to reach it for
    a watched one alone). The edge is live: the next wave through the row
    reaches it."""
    hub = FusionHub()
    backend = TpuGraphBackend(hub, node_capacity=256, edge_capacity=1024)
    svc = Chain(hub, 64)
    hub.add_service(svc)
    table = memo_table_of(svc.val)
    block = backend.bind_table_rows(table)
    backend.declare_row_edges(block, np.arange(31), block, np.arange(1, 32))  # 0 -> .. -> 31
    old = set_default_hub(hub)
    try:
        table.read_batch(np.arange(64))
        twin = await capture(lambda: svc.val(10))
        if watched:
            twin.on_invalidated(lambda _c: None)
        backend.flush()
        assert backend.cascade_rows_batch(block, [10]) == 22
        assert twin.is_invalidated
        backend.declare_row_edges(block, np.array([10]), block, np.array([40]))
        backend.flush()
        assert not np.asarray(backend.graph.invalid_mask())[40]
        assert not table._stale_host[40]
        assert backend.wave_echo_marks_dropped == (1 if watched else 0)
        assert backend.cascade_rows_batch(block, [10]) == 1  # row 40, through the new edge
        assert table._stale_host[40]
    finally:
        set_default_hub(old)
